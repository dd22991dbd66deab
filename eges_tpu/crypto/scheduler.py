"""Mesh-sharded coalescing verifier scheduler with a sender-recovery cache.

Every consensus/txpool call site used to drive the batch verifier
synchronously — including one-row dispatches per candidacy/registration
message that got padded to a 16-row bucket and still paid full dispatch
plus transfer cost.  This layer sits between those callers and the
device facade (:class:`~eges_tpu.crypto.verifier.BatchVerifier` or the
JAX-free :class:`~eges_tpu.crypto.verify_host.NativeBatchVerifier`):

* rows enter ONE way, as a WINDOW: every synchronous facade
  (``recover_signers``, ``recover_addresses``, ``recover_window``), the
  pool's ``submit_window`` and the single-row :meth:`submit` (a one-row
  window behind a future) go through the one routine ``_enter_window``:
  one lock hold, one batched cache probe and in-flight dedup sweep, one
  result holder, one wake-up, so the dispatcher never sees half a
  quorum;
* a background dispatch thread coalesces concurrent requests across
  callers (txpool sender recovery + vote quorums + single-message
  checks) into ONE batch per micro-window — flushed when the bucket
  fills, when the deadline measured from the oldest pending entry
  expires, or when a synchronous caller *kicks* the window;
* an LRU ``(sighash, sig) -> address-or-None`` recovery cache makes
  gossip re-delivery and commit-time re-verification free — the role
  split the reference implements host-side as the concurrent sender
  cacher + signature LRU (ref: core/tx_cacher.go:45 txSenderCacher,
  core/types/transaction_signing.go:42 sigCache via Transaction.from);
* a flush that coalesced down to a single row is diverted to the host
  recovery path instead of the device: a padded 1-row device dispatch
  costs more than one native recover, and diverting keeps
  ``verifier.singleton_batches`` at zero in steady state.  A
  consensus-class flush of a FEW rows joins it where its lane's target
  would pad it to a bucket at least ``HOST_WINDOW_RATIO`` times its
  rows (up to 8 rows under the kernel path's 256-row floor; never on a
  16-row ladder, never on a target that reports no bucket: the native
  verifiers): what such a window costs on the device is the round trip,
  not the rows, and a quorum is waiting for it.  It is answered inline
  on the dispatch thread in ONE native call for all of its rows
  (:meth:`VerifierScheduler._host_served`).  Bulk windows keep the
  one-row rule alone.

**Mesh dispatch.** When the backing verifier exposes ``device_targets()``
(:class:`~eges_tpu.crypto.verifier.MeshBatchVerifier`, or the host-model
``NativeMeshVerifier``), the admission front above feeds one *window
lane* per device instead of calling the verifier inline:

* each lane owns a FIFO queue and a worker thread, so a slow chip
  stalls only the windows placed on it (stragglers never head-of-line
  block the mesh);
* placement fills the least-loaded lane (queued + in-flight rows; ties
  rotate round-robin so idle meshes still spread sequential windows),
  and a window larger than ``max_batch / n_lanes`` splits into
  contiguous chunks across distinct lanes — saturated load reaches
  every device;
* the PR 5 circuit breaker is scoped PER LANE: one dead device trips
  one breaker, that lane's windows host-divert, every other lane keeps
  the device path (per-lane ``straggler_diverts`` counts the rescue);
* completion is per chunk — each chunk resolves (or fails) its own
  rows independently, reusing the fail-safe resolution, so one
  device's death diverts exactly its own in-flight windows.

With one visible device the lane machinery collapses to the PR 4/5
behavior: the admission thread dispatches inline, no extra threads.

**Double-buffered window pipeline.** A target exposing the split-phase
``stage_recover`` / ``commit_recover`` / ``collect_recover`` trio
(:class:`~eges_tpu.crypto.verifier.BatchVerifier` and its mesh lane
facades) gets its windows run on a lane worker even single-lane: the
worker begins window k+1 — numpy fill, H2D upload into the verifier's
double buffers, async device dispatch — BEFORE blocking on window k's
collect, so consecutive windows overlap H2D/compute/D2H instead of
serializing.  ``verifier.pipeline_overlap_ratio`` (and per-lane
``pipeline_windows``/``pipeline_overlapped`` stats) report how often
the overlap actually happened.  Native verifiers don't expose the trio,
so sims and the chaos harness keep the inline path and its
byte-deterministic event ordering.

**Priority classes and hedging.** The window policy is static: the
keyword arguments of :class:`VerifierScheduler` are the only way to set
it (no environment variable is read, nothing retunes it at run time).
Windows carry a priority class: ``"consensus"`` submissions (election
acks, QC checks) flush ahead of ``"bulk"`` tx-ingest rows and their
windows preempt bulk windows at lane placement, with per-class
queue-wait metrics.  In mesh mode a straggler monitor hedges: a window
whose wall-clock age exceeds its lane's flight-derived threshold
(median × ``HEDGE_FACTOR``) is speculatively re-placed on the
least-loaded sibling lane; the first result wins, the loser is
cancelled (or its results discarded), and stats/journal/ledger all
record the window exactly once.

This module must stay importable WITHOUT JAX (same contract as
``verify_host.py``): host-fallback node processes and the benchmark's
parent construct schedulers around native verifiers.

Thread model: ``submit``/``submit_window``/the synchronous facades/
``kick``/``close`` arrive on any caller thread
(RPC workers, the sim clock thread, consensus dispatch); the flush loop
runs on one daemon thread, plus one daemon worker per device lane in
mesh mode.  Every mutable field — pending map, cache, stats, every lane
queue and breaker — is guarded by the one condition ``self._lock``; the
dispatch and lane threads call only the backing verifier outside it,
never a caller's lock — so they can never deadlock against the
node/txpool lock domain.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, OrderedDict, deque
from concurrent.futures import Future

import numpy as np

from eges_tpu.crypto.bucketing import bucket_round, lane_chunk_cap
from eges_tpu.utils import heap, ledger, tracing

# sentinel distinguishing "cached None" (a signature that verifiably
# fails recovery) from "not cached"
_MISS = object()

# the shared bucket model (back-compat alias: scheduler and verifier
# both round through crypto/bucketing.bucket_round now)
_bucket16 = bucket_round


class _WindowRows:
    """Result holder for one window-granular submission: N rows, ONE
    completion — :meth:`VerifierScheduler.submit_window` returns one of
    these instead of N per-row futures, so a 16k-row ingest window
    costs one wait-side object and one wakeup.

    Each row that has to be computed rides the pending map as a
    ``(window, index)`` pair; the backing future resolves with the full
    ``results`` list once every row has resolved.  Row failures are stored as
    exception VALUES (never raised here) so one dead row cannot poison
    its window — callers decide per row (``recover_window`` host-
    diverts them, mirroring ``recover_signers``)."""

    __slots__ = ("results", "cached", "coalesced", "_done", "_remaining",
                 "_lock", "_fut", "_finished")

    def __init__(self, n: int):
        self.results: list = [None] * n
        # what answered the window when it entered (``_enter_window``):
        # rows the recovery cache held, rows that joined a row already
        # pending; the others ride a batch of their own
        self.cached = 0
        self.coalesced = 0
        self._done = bytearray(n)
        self._remaining = n
        self._lock = threading.Lock()
        self._fut: Future = Future()
        self._finished = False

    def _set_rows(self, idxs, values) -> None:
        """Write the rows ``idxs`` not yet written and complete the
        window when they were its last: ONE lock hold however many rows
        (a resolving batch hands over all of its rows of this window
        together).  A row is written exactly once: a hedge loser finds
        every row done and changes nothing."""
        with self._lock:
            done, results = self._done, self.results
            n = 0
            for i, v in zip(idxs, values):
                if not done[i]:
                    done[i] = 1
                    results[i] = v
                    n += 1
            self._remaining -= n
            if self._remaining or self._finished:
                return
            self._finished = True
        self._fut.set_result(self.results)

    def prefill(self, idx: int, value) -> None:
        """Construction-time row fill (cache hits, post-close rows) —
        called before any row of this window is visible to the lanes,
        so the row lock is uncontended; taken anyway to keep every
        write to the shared rows under the same lock.  The window
        future completes later via :meth:`_try_finish`."""
        with self._lock:
            self._done[idx] = 1
            self.results[idx] = value
            self._remaining -= 1

    def _try_finish(self) -> None:
        self._set_rows((), ())

    def result(self, timeout: float | None = None) -> list:
        return self._fut.result(timeout)

    def add_done_callback(self, fn) -> None:
        """``fn(window)`` once every row has its value, on the thread
        that set the last one (a lane worker, the dispatcher, or the
        caller's own where the window completed as it entered): ``fn``
        hands the window on and returns; it never blocks."""
        self._fut.add_done_callback(lambda _fut: fn(self))


class WindowAnswers(list):
    """A synchronous window call's answers, one a row, with what the
    window's holder knew when it entered: ``cached`` rows the recovery
    cache answered, ``coalesced`` rows that joined a row already pending
    (a window in flight, or an earlier row of the same call)."""

    __slots__ = ("cached", "coalesced")

    def __init__(self, win: _WindowRows):
        super().__init__(win.results)
        self.cached = win.cached
        self.coalesced = win.coalesced


class RecoveredAddresses(tuple):
    """``(addrs, ok)`` as ``BatchVerifier.recover_addresses`` returns
    them, with the :class:`WindowAnswers` counts of the window that
    carried the call."""

    def __new__(cls, addrs, ok, answers: WindowAnswers):
        self = super().__new__(cls, (addrs, ok))
        self.cached = answers.cached
        self.coalesced = answers.coalesced
        return self


def _class_of(priority: str) -> str:
    """The scheduler's two priority classes: ``consensus`` by name,
    everything else ``bulk``."""
    return "consensus" if priority == "consensus" else "bulk"


# A synchronous call of this many rows is a ``burst`` to its spans'
# ``size`` label (a committee's ACK replies), a smaller one a ``call``
# (an election's votes, a header, a pool slice): their means are not to
# be mixed.
BURST_ROWS = 1000

# A consensus-class window of a few rows is answered on the host, as a
# one-row window is, when its lane's target would pad it to a bucket of
# at least this many times its rows (``VerifierScheduler._host_served``):
# up to 8 rows under the kernel path's 256-row floor, none on a 16-row
# ladder.  A constant, not a policy knob; PERF.md has the crossover,
# measured on the chip's host.
HOST_WINDOW_RATIO = 32

# A lane's window is a straggler, and is hedged onto a sibling lane, once
# its age exceeds this many medians of the lane's recent window totals.
HEDGE_FACTOR = 3.0


def _call_labels(priority: str, rows: int) -> dict:
    """Attributes of a synchronous call's ``sched.submit`` and
    ``sched.await`` spans; ``class`` and ``size`` label the histograms."""
    return {"rows": rows, "class": _class_of(priority),
            "size": "burst" if rows >= BURST_ROWS else "call"}


def _array_keys(hashes: np.ndarray, sigs: np.ndarray) -> list:
    """Row keys of a columnar window: ``hashes`` (n,32) / ``sigs``
    (n,65) uint8 rows as ``(bytes, bytes)`` pairs, cut from ONE copy of
    each array instead of one numpy row object a key."""
    n = len(hashes)
    if n == 0:
        return []
    if hashes.shape[1] != 32 or sigs.shape[1] != 65:
        raise ValueError("window arrays must be (n,32) and (n,65)")
    hb, sb = hashes.tobytes(), sigs.tobytes()
    return [(hb[i * 32:i * 32 + 32], sb[i * 65:i * 65 + 65])
            for i in range(n)]


def _key_arrays(keys: list) -> tuple[np.ndarray, np.ndarray]:
    """The inverse of :func:`_array_keys`: a window's row keys as
    ``(hashes (n,32), sigs (n,65))`` uint8 arrays, ONE join and one
    buffer view a column instead of two row assignments a key.  The
    views are read-only: a target copies them into its staging buffers."""
    n = len(keys)
    hashes = np.frombuffer(b"".join([k[0] for k in keys]), np.uint8)
    sigs = np.frombuffer(b"".join([k[1] for k in keys]), np.uint8)
    return hashes.reshape(n, 32), sigs.reshape(n, 65)


def _row_results(addrs, ok) -> list:
    """A target's ``(addrs (n,20) uint8, ok (n,))`` as one result a row
    (the 20-byte address, ``None`` for an invalid row), cut from ONE
    copy of each array instead of one numpy row object a result."""
    ab = np.asarray(addrs, np.uint8).tobytes()
    return [ab[i * 20:i * 20 + 20] if good else None
            for i, good in enumerate(np.asarray(ok).tolist())]


def host_recover_rows(keys) -> list:
    """The host recovery path: the rows of ``keys`` (``(sighash32,
    sig65)`` pairs) in ONE native call (``ec_recover_batch``: the rows
    in parallel, no GIL held) when the C++ library is built, the
    pure-Python model a row otherwise; one 20-byte address or None a
    row.  What a scheduler answers by when the device cannot, and what
    a sidecar's client answers by when the sidecar cannot.  Counts into
    ``verifier.host_rows`` like every other host fallback so the
    device-share metric stays honest."""
    from eges_tpu.crypto.verify_host import _count_host_rows
    n = len(keys)
    _count_host_rows(n)
    from eges_tpu.crypto import native
    if native.available():
        from eges_tpu.crypto.keccak import keccak256
        pubs, okb = native.ec_recover_batch(
            b"".join([k[0] for k in keys]),
            b"".join([k[1] for k in keys]), n)
        return [keccak256(pubs[64 * i:64 * i + 64])[12:]
                if okb[i] else None for i in range(n)]
    from eges_tpu.crypto import secp256k1 as host
    out = []
    for h, sig in keys:
        try:
            out.append(host.recover_address(h, sig))
        # analysis: allow-swallow(invalid signature maps to a None result)
        except Exception:
            out.append(None)
    return out


class _DeviceLane:
    """One device's window queue + dispatch bookkeeping (a mesh lane).

    Single-device schedulers have exactly one lane driven inline by the
    admission thread; in mesh mode each lane owns a worker thread
    draining its queue, so one slow or dead device stalls only the
    windows placed on it.  Every field here is guarded by the owning
    scheduler's ``self._lock``.
    """

    __slots__ = ("index", "target", "queue", "thread", "breaker",
                 "breaker_until", "inflight_rows", "queued_rows",
                 "max_queue_depth", "stats")

    def __init__(self, index: int, target):
        self.index = index
        self.target = target
        self.queue: deque = deque()  # (batch, reason)
        self.thread: threading.Thread | None = None
        self.breaker = "closed"      # "closed" | "open"
        self.breaker_until = 0.0
        self.inflight_rows = 0       # rows at the device right now
        self.queued_rows = 0         # rows waiting in self.queue
        self.max_queue_depth = 0     # high-water of len(self.queue)
        self.stats = {
            "batches": 0, "rows": 0, "bucket_rows": 0,
            "host_diverted": 0, "straggler_diverts": 0,
            "device_errors": 0, "breaker_trips": 0,
            "breaker_probes": 0, "breaker_diverted": 0,
            "pipeline_windows": 0, "pipeline_overlapped": 0,
        }

    def load(self) -> int:
        """Placement score: rows waiting plus rows in flight."""
        return self.queued_rows + self.inflight_rows


class _PendingWindow:
    """One window's begin-to-finish state in the split-phase pipeline.

    ``_begin_batch`` fills it (and, on a pipeline-capable target, leaves
    the staged+dispatched device computation in ``staged``);
    ``_finish_batch`` collects, records and resolves it.  A lane worker
    holds at most ONE of these in flight — beginning window k+1 before
    finishing window k is exactly the H2D/compute/D2H overlap.
    """

    __slots__ = ("batch", "keys", "reason", "t0", "rows", "results",
                 "staged", "probing", "diverted", "computed", "failure",
                 "finished", "t_dispatch", "t_collect", "ticket", "flight",
                 "hosted", "t_flush")


class _WindowTicket:
    """Shared placement identity for one mesh window and (when hedged)
    its speculative duplicate.

    Lane queues hold tickets; the straggler monitor re-places a ticket
    whose wall-clock age exceeds its lane's flight-derived threshold
    onto the least-loaded sibling lane, so the SAME ticket can sit in
    two queues at once.  ``winner`` is claimed under the scheduler lock
    by the first dispatch to finish: the loser is either *cancelled*
    (still queued at claim time — dropped at pop, never touches a
    device) or *wasted* (already executing — its results are discarded
    and it skips ``_record_window``, so stats, journal events, flight
    entries and ledger charges all happen exactly once per window).
    Every field is guarded by the owning scheduler's ``self._lock``
    except ``batch``/``reason``/``klass``/``rows``/``lane``/``t_flush``,
    which are immutable after construction.
    """

    __slots__ = ("batch", "reason", "klass", "rows", "lane", "t_flush",
                 "hedge_lane", "t_placed", "hedged", "winner")

    def __init__(self, batch, reason: str, klass: str, lane: int,
                 t_flush: float):
        self.batch = batch
        self.reason = reason
        # when the dispatcher popped the window this chunk was cut from
        self.t_flush = t_flush
        self.klass = klass           # "consensus" | "bulk"
        self.rows = len(batch)
        self.lane = lane             # primary placement lane index
        self.hedge_lane = None       # sibling index once hedged
        # Straggler aging is wall-clock by nature: a stuck lane freezes
        # the sim's virtual clock, so a virtual-time age could never
        # fire.  Hedges journal nothing, so determinism holds.
        # analysis: allow-determinism(hedge aging; hedges journal nothing)
        self.t_placed = time.monotonic()
        self.hedged = False
        self.winner = None           # winning lane index once recorded


class VerifierScheduler:
    """Coalescing dispatch front-end over a batch verifier.

    Facade-compatible with the verifier it wraps: ``recover_addresses``
    / ``recover_signers`` / ``ecrecover`` / ``verify`` all exist, so the
    chain, txpool, EVM precompile, and consensus node can hold a
    scheduler wherever they previously held a ``BatchVerifier``.

    The keyword arguments are the whole policy surface, and the only
    way to set it: no config object, no environment variable (a stray
    one in an operator's shell must not change a validator's window
    policy), nothing retuned at run time.

    * ``window_ms`` — flush deadline from the oldest pending entry;
    * ``max_batch`` — hard bucket cap per window;
    * ``cache_size`` — LRU recovery-cache entries;
    * ``breaker_cooldown_s`` — per-lane breaker open time;
    * ``min_split`` — smallest mesh chunk worth a dispatch;
    * ``flight_ring`` — flight-recorder ring capacity (the most
      ``thw_flight`` hands out at once);
    * ``hedge`` — speculative straggler re-placement (mesh only);
    * ``hedge_min_windows`` — lane flights before its own median
      outranks the all-lane median;
    * ``hedge_floor_ms`` — never hedge a window younger than this;
    * ``hedge_poll_ms`` — straggler monitor poll period;
    * ``breaker_clock`` — injectable clock of the breaker's cooldown.
    """

    def __init__(self, verifier, *, window_ms: float = 2.0,
                 max_batch: int = 1024, cache_size: int = 4096,
                 breaker_cooldown_s: float = 5.0, min_split: int = 16,
                 flight_ring: int = 4096, hedge: bool = True,
                 hedge_min_windows: int = 4, hedge_floor_ms: float = 25.0,
                 hedge_poll_ms: float = 5.0, breaker_clock=None):
        self._verifier = verifier
        self._window_s = window_ms / 1e3
        self.max_batch = max_batch
        self.cache_size = cache_size
        # injectable device-failure hook (chaos harness / tests): called
        # with the row count right before every device dispatch, on any
        # lane; raising is treated exactly like the device itself
        # raising.  Per-lane kills go through the lane target's own
        # ``failure_hook`` instead.
        self.failure_hook = None
        # circuit breaker around each lane's device path: a device
        # exception trips that lane OPEN (its windows host-divert, no
        # device calls) for ``breaker_cooldown_s``; the first window
        # after the cooldown is a HALF-OPEN probe — success closes the
        # lane's breaker, failure re-opens it.  ``breaker_clock`` is
        # injectable so chaos runs can measure the cooldown in
        # deterministic virtual time.
        self.breaker_cooldown_s = breaker_cooldown_s
        self.breaker_clock = breaker_clock or time.monotonic
        # ONE condition guards every mutable field below (including all
        # lane queues); dispatch + lane threads wait on it.
        self._lock = threading.Condition()
        # one window lane per device the verifier exposes; a verifier
        # without device_targets() is itself the single lane's target
        targets = None
        probe = getattr(verifier, "device_targets", None)
        if callable(probe):
            targets = list(probe())
        if not targets:
            targets = [verifier]
        self._lanes = [_DeviceLane(i, t) for i, t in enumerate(targets)]
        # double-buffered pipeline capability: targets exposing the
        # split-phase stage/commit/collect trio get their windows run
        # on a lane worker even single-lane, so window k+1's H2D
        # staging overlaps window k's compute + D2H.  Native verifiers
        # don't expose it — sims keep the inline path and its
        # byte-deterministic event ordering.
        self._pipelined = any(
            callable(getattr(lane.target, "stage_recover", None))
            for lane in self._lanes)
        # placement: a window larger than this splits across lanes
        # (floor min_split keeps chunks worth a device dispatch)
        self.min_split = max(1, min_split)
        self._chunk_cap = lane_chunk_cap(max_batch, len(self._lanes),
                                         self.min_split)
        self._rr = 0  # round-robin cursor breaking equal-load ties
        # LRU recovery cache: (sighash, sig) -> 20-byte address or None
        # (a deterministic recovery failure is cached too — re-gossiped
        # garbage must not re-reach the device either)
        self._cache: OrderedDict[tuple, object] = OrderedDict()  # guarded-by: _lock
        # key -> [holders, t_submit, klass], each holder the
        # (_WindowRows, index) pair of a row that waits for this key:
        # identical in-flight keys share one row (in-batch dedup),
        # arrival order preserved.
        # ``klass`` is the priority class ("consensus" | "bulk"): dedup
        # promotes a shared row to the higher class, and the flush
        # selects consensus rows first when the window cannot take
        # everything pending.
        self._pending: OrderedDict[tuple, list] = OrderedDict()  # guarded-by: _lock
        # key -> trace id of the submitter's active span (txpool ingest,
        # quorum verify): commit-anatomy linkage tying flight-recorder
        # windows back to the transactions that rode them.  Bounded like
        # the ingest-context map; entries pop when their window records.
        self._pending_trace: dict[tuple, str] = {}  # guarded-by: _lock
        self._PENDING_TRACE_CAP = 8192
        # key -> (ledger, origin) captured at submit (utils/ledger.py):
        # the window executes on the dispatch/lane thread where the
        # submitter's ambient binding is gone, so each row's share of
        # the window cost charges the captured pair when it records.
        # Same cap discipline as the trace map; entries pop with their
        # window (in-flight dedup keeps the FIRST submitter's origin).
        self._pending_origin: dict[tuple, tuple] = {}  # guarded-by: _lock
        # cache-served rows since the last recorded window: cache hits
        # never reach a window, so without this the flight rows (and the
        # cheap-reject cost math over them) under-count a warm-cache
        # flood as free — drained into flight["cache_rows"]
        self._cache_rows_pending = 0  # guarded-by: _lock
        # in-flight-deduped rows since the last recorded window — the
        # same drain discipline as cache rows, feeding the goodput
        # ledger's waste decomposition (utils/devstats.py)
        self._dedup_rows_pending = 0  # guarded-by: _lock
        self._kick = False  # guarded-by: _lock
        self._closed = False
        # set once the dispatch loop exits
        self._admission_done = False  # guarded-by: _lock
        self._thread: threading.Thread | None = None
        self._stats = {  # guarded-by: _lock
            "cache_hits": 0, "cache_misses": 0, "cache_served_rows": 0,
            "coalesced_rows": 0,
            "batches": 0, "rows": 0, "bucket_rows": 0,
            # windows answered on the host BY RULE (one row, or a small
            # consensus window: _host_served), and their rows
            "host_diverted": 0, "host_diverted_rows": 0,
            "kicks": 0, "flush_full": 0, "flush_deadline": 0,
            "flush_kick": 0, "flush_close": 0, "invalid": 0,
            "device_errors": 0, "breaker_trips": 0, "breaker_probes": 0,
            "breaker_diverted": 0, "window_splits": 0,
            "straggler_diverts": 0, "pipeline_windows": 0,
            "pipeline_overlapped": 0,
            # lock holds that answered the recorded windows' holders:
            # one a _WindowRows of the batch; ``rows`` over this is the
            # rows answered a hold
            "resolve_holds": 0,
            # hedged re-dispatch accounting: every hedge ends as either
            # a cancelled loser (never ran) or a wasted loser (ran,
            # discarded) — hedges == hedge_cancelled + hedge_wasted at
            # quiescence is the exactly-once recording invariant
            "hedges": 0, "hedge_wins": 0, "hedge_cancelled": 0,
            "hedge_wasted": 0,
            # flight-ring loss accounting
            "flight_dropped": 0,
            # window-granular admissions (_enter_window): whole ingest
            # windows and synchronous calls entering in ONE lock hold
            # instead of row-by-row, by the priority class the caller
            # gave (_class_of); stats() adds the two classes up as
            # ``window_submits`` / ``window_rows``
            "window_submits_consensus": 0, "window_rows_consensus": 0,
            "window_submits_bulk": 0, "window_rows_bulk": 0,
        }
        # optional consensus event journal (utils/journal.py), attached
        # by the first owning node; flush decisions land in its stream
        self.journal = None
        # window flight recorder: every computed window's
        # submit->place->stage->compute->collect->resolve lifecycle with
        # lane/device attribution, in a bounded ring behind the
        # thw_flight RPC and the observatory waterfall.  Wall-clock by
        # nature (it measures real phase durations) and never journaled,
        # so it stays outside the determinism contract.  The ring size
        # is configurable (flight_ring) and an append that evicts the
        # oldest entry counts into stats["flight_dropped"] +
        # verifier.flight_dropped — silent loss under load is visible.
        self._flights: deque = deque(maxlen=max(1, flight_ring))  # guarded-by: _lock
        self._flight_seq = 0  # guarded-by: _lock
        # per-class queue-wait samples (ms) behind stats()'s
        # class_wait_ms percentiles
        self._class_waits = {
            "bulk": deque(maxlen=2048),
            "consensus": deque(maxlen=2048),
        }  # guarded-by: _lock
        # hedged re-dispatch: live (unrecorded) window tickets the
        # straggler monitor scans; mesh-only — with one lane there is
        # no sibling to hedge onto
        self._hedge_on = bool(hedge) and len(self._lanes) > 1
        self.hedge_min_windows = hedge_min_windows
        self.hedge_floor_ms = hedge_floor_ms
        self._hedge_poll_s = max(0.5e-3, hedge_poll_ms / 1e3)
        self._tickets: set = set()  # guarded-by: _lock
        self._hedge_thread: threading.Thread | None = None
        if len(self._lanes) > 1:
            from eges_tpu.utils.metrics import DEFAULT as metrics
            metrics.gauge("verifier.mesh_devices").set(len(self._lanes))

    # -- public async API -------------------------------------------------

    def submit(self, sighash: bytes, sig: bytes,
               priority: str = "bulk") -> Future:  # thread-entry hot-path-entry
        """Queue one ``(sighash32, sig65)`` recovery; the future resolves
        to the 20-byte signer address, or ``None`` for an invalid
        signature.  A one-row window (:meth:`_enter_window`) behind a
        future: a cache hit resolves immediately, a miss rides the next
        coalesced batch, and a batch that died FAILS the future with its
        error rather than answering ``None`` ("invalid signature").  A
        caller that has a batch in hand takes a window entry
        (:meth:`recover_signers`, :meth:`submit_window`), which costs
        one lock hold for all of it, not one a row.

        ``priority`` is the window class: ``"consensus"`` rows
        (election acks, QC checks — anything consensus blocks on) are
        flushed ahead of ``"bulk"`` tx-ingest rows when a window can't
        take everything pending, and their windows preempt bulk windows
        at lane placement.  In-flight dedup promotes a shared row to
        the higher class."""
        key = ((bytes(sighash), bytes(sig))
               if len(sig) == 65 and len(sighash) == 32 else None)
        fut: Future = Future()

        def _row_done(done) -> None:  # the window's completed future
            value = done.result()[0]
            if isinstance(value, BaseException):
                fut.set_exception(value)
            else:
                fut.set_result(value)

        self._enter_window([key], priority)._fut.add_done_callback(_row_done)
        return fut

    def kick(self) -> None:  # thread-entry hot-path-entry
        """Flush the current micro-window immediately: synchronous
        callers (quorum tallies under the virtual-time sim clock) must
        not sleep out the real-time deadline."""
        with self._lock:
            if self._pending:
                self._kick = True
                self._stats["kicks"] += 1
                self._lock.notify_all()

    # -- window entry + synchronous facades (BatchVerifier-compatible) ----

    def _enter_window(self, keys: list, priority: str) -> _WindowRows:
        """A window of rows enters: THE one implementation, behind
        :meth:`submit_window` and every synchronous facade.  ``keys``
        holds one ``(sighash32, sig65)`` pair of ``bytes`` a row, or
        ``None`` for a malformed entry.  ONE lock acquisition covers the
        batched cache probe (with LRU touch), post-close inline
        recovery, the in-flight dedup sweep with class promotion, the
        trace-id and ledger-origin capture and the aggregated stats;
        one wake-up, then one ``ledger.charge`` for the whole window (N
        unit charges at one timestamp sum to the same ledger state).
        A malformed entry answers ``None``, counts as ``invalid``, is
        billed as a reject and never reaches the device (the zero-fill
        rows of ``verify_host.recover_signers`` recover as invalid: the
        same observable result, no batch slot burned)."""
        from eges_tpu.utils.metrics import DEFAULT as metrics

        n = len(keys)
        win = _WindowRows(n)
        if n == 0:
            win._try_finish()
            return win
        klass = _class_of(priority)
        n_hits = n_invalid = n_joined = 0
        with self._lock:
            # analysis: allow-determinism(coalescing deadline is real-time by contract; chaos pins batching via max_batch kicks)
            t_now = time.monotonic()
            ctx = tracing.DEFAULT.current_context()
            tid = ctx.trace_id if ctx is not None else None
            rec = ledger.current()
            added = False
            for i, key in enumerate(keys):
                if key is None:
                    n_invalid += 1
                    win.prefill(i, None)
                    continue
                hit = self._cache.get(key, _MISS)
                if hit is not _MISS:
                    self._cache.move_to_end(key)
                    n_hits += 1
                    win.prefill(i, hit)
                    continue
                if self._closed:
                    # post-close stragglers execute inline on the
                    # caller — the contract is "no lost rows", not "no
                    # work"
                    v = self._host_recover(key)
                    self._cache_put_many((key,), (v,))
                    win.prefill(i, v)
                    continue
                row = self._pending.get(key)
                if row is not None:
                    # in-flight dedup (intra-window duplicates land
                    # here too: the first occurrence owns the batch
                    # row, later ones share it)
                    row[0].append((win, i))
                    n_joined += 1
                    if klass == "consensus":
                        row[2] = "consensus"
                else:
                    self._pending[key] = [[(win, i)], t_now, klass]
                    if (tid is not None and len(self._pending_trace)
                            < self._PENDING_TRACE_CAP):
                        self._pending_trace[key] = tid
                    if (rec is not None and len(self._pending_origin)
                            < self._PENDING_TRACE_CAP):
                        self._pending_origin[key] = rec
                    added = True
            n_miss = n - n_hits - n_invalid
            win.cached, win.coalesced = n_hits, n_joined
            self._stats["coalesced_rows"] += n_joined
            self._dedup_rows_pending += n_joined
            # a cache-served row is still a served row: without this
            # accounting a 100% warm-cache flood looks free in
            # stats()/flight rows (drained into the next window's
            # flight entry as cache_rows)
            self._cache_rows_pending += n_hits
            self._stats["cache_hits"] += n_hits
            self._stats["cache_served_rows"] += n_hits
            self._stats["cache_misses"] += n_miss
            self._stats["invalid"] += n_invalid
            self._stats["window_submits_" + klass] += 1
            self._stats["window_rows_" + klass] += n
            if added:
                self._ensure_thread()
            if len(self._pending) >= self.max_batch:
                self._kick = True
            self._lock.notify_all()
        if n_hits:
            metrics.counter("verifier.cache_hits").inc(n_hits)
        if n_miss:
            metrics.counter("verifier.cache_misses").inc(n_miss)
        # the invalid-sig early-out is the cheapest reject there is,
        # which is exactly why a flood of them must stay attributed
        ledger.charge(cache_hits=n_hits, cache_misses=n_miss,
                      rejects=n_invalid)
        win._try_finish()  # all-prefilled windows complete right here
        return win

    def _await_window(self, win: _WindowRows, keys: list,
                      labels: dict) -> WindowAnswers:
        """The blocking half of a synchronous call: one kick, one wait.
        A row that a torn-down scheduler (or a window that died on its
        way) failed is recovered on the host: consensus keeps
        committing."""
        with tracing.DEFAULT.span("sched.await", **labels):
            self.kick()
            win.result()
        out = WindowAnswers(win)
        for i, v in enumerate(out):
            if isinstance(v, BaseException):
                out[i] = self._host_recover(keys[i])
        return out

    def recover_signers(self, entries, *, priority: str = "bulk") -> list:
        """Batch-recover ``(sighash32, sig65)`` entries; one 20-byte
        address or ``None`` per entry.  The whole call enters as ONE
        window (:meth:`_enter_window`: one lock hold, no per-row future),
        kicks it (coalescing with whatever else is pending right now)
        and blocks for the results, so the dispatcher sees a quorum's
        rows all at once and never cuts a call by its deadline while it
        is still being handed over.  A call of more than ``max_batch``
        rows is cut by the dispatcher, consensus-class rows first.
        ``verify_host.recover_signers`` delegates here when the node's
        verifier is a scheduler.  ``priority="consensus"`` marks the
        rows consensus-critical (see :meth:`submit`)."""
        labels = _call_labels(priority, len(entries))
        with tracing.DEFAULT.span("sched.submit", **labels):
            keys = [(bytes(h), bytes(s))
                    if len(s) == 65 and len(h) == 32 else None
                    for h, s in entries]
            win = self._enter_window(keys, priority)
        return self._await_window(win, keys, labels)

    def recover_addresses(self, sigs: np.ndarray, hashes: np.ndarray,
                          *, priority: str = "bulk"):
        """Array-in/array-out facade matching
        ``BatchVerifier.recover_addresses`` so block body validation
        and the EVM ecrecover precompile route through the
        cache/coalescer unchanged: the arrays take the window path
        (:meth:`recover_window`) as they are.  The pair that comes back
        is a :class:`RecoveredAddresses`: it also says how many of the
        rows the cache answered and how many joined a pending row."""
        n = sigs.shape[0]
        addrs = np.zeros((n, 20), np.uint8)
        ok = np.zeros((n,), bool)
        if n == 0:
            return addrs, ok
        answers = self.recover_window(hashes, sigs, priority=priority)
        for i, r in enumerate(answers):
            if r is not None:
                addrs[i] = np.frombuffer(r, np.uint8)
                ok[i] = True
        return RecoveredAddresses(addrs, ok, answers)

    def submit_window(self, hashes: np.ndarray, sigs: np.ndarray,
                      priority: str = "bulk") -> _WindowRows:
        """Window-granular :meth:`submit`: a whole columnar window
        (``hashes`` (n,32) / ``sigs`` (n,65) uint8 rows) enters through
        :meth:`_enter_window` under the ``sched.submit`` span and comes
        back as ONE :class:`_WindowRows` instead of N row futures; the
        caller does not wait.  The verify sidecar's server
        (``crypto/sidecar.py``) is its caller: a connection's reader
        enters each request here with the client's priority, kicks, and
        goes on to the next frame, so several windows of several node
        processes are in flight at once and each answer goes back when
        its window resolves (:meth:`_WindowRows.add_done_callback`).
        :meth:`recover_window` is the synchronous facade an in-process
        caller takes.  A row that a torn-down scheduler failed stays an
        exception VALUE in ``results``: the sidecar's client recovers it
        on its own host, as :meth:`_await_window` does here."""
        with tracing.DEFAULT.span("sched.submit",
                                  **_call_labels(priority, len(hashes))):
            return self._enter_window(_array_keys(hashes, sigs), priority)

    def recover_window(self, hashes: np.ndarray, sigs: np.ndarray,
                       *, priority: str = "bulk") -> list:
        """Synchronous window facade over arrays: the window entry, one
        kick, one blocking wait — ``verify_host.recover_signers_window``
        delegates here when the pool's verifier is a scheduler.  Rows a
        torn-down scheduler failed fall back to host recovery, exactly
        like :meth:`recover_signers`.  Both return a
        :class:`WindowAnswers`: the list, with the window's ``cached``
        and ``coalesced`` counts."""
        labels = _call_labels(priority, len(hashes))
        with tracing.DEFAULT.span("sched.submit", **labels):
            keys = _array_keys(hashes, sigs)
            win = self._enter_window(keys, priority)
        return self._await_window(win, keys, labels)

    def ecrecover(self, sigs: np.ndarray, hashes: np.ndarray):
        """Full-pubkey recovery delegates straight to the backing
        verifier: the cache stores addresses only (the sigCache role),
        and the sole ``pubs`` consumer is the startup warmup."""
        return self._verifier.ecrecover(sigs, hashes)

    def verify(self, sigs: np.ndarray, hashes: np.ndarray,
               pubs: np.ndarray):
        """Classic known-pubkey verify is not address recovery — pass
        through to the backing verifier's batched path."""
        return self._verifier.verify(sigs, hashes, pubs)

    # -- lifecycle --------------------------------------------------------

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def close(self, timeout: float | None = 30.0) -> None:  # thread-entry
        """Drain every pending row, then stop and join every thread —
        no lost rows, no leaked threads.

        The drain order is deterministic and documented:

        1. the admission front flushes whatever is pending as one final
           ``flush_close`` window (placed/run like any other) and the
           dispatch thread exits;
        2. each device lane drains its queue FIFO — lane workers exit
           only after the admission thread is done, so a final window
           placed during shutdown is always served — and lanes are
           joined in ascending device index;
        3. anything still unresolved (a dead thread or a join timeout)
           is FAILED rather than left to hang callers: lane queues
           first in ascending device index (FIFO within each lane), the
           admission front last.
        """
        with self._lock:
            self._closed = True
            self._kick = True
            self._lock.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout)
        with self._lock:
            # the admission thread sets this on exit; force it if the
            # thread never ran or the join timed out, so lane workers
            # can stop waiting for more placements
            self._admission_done = True
            self._lock.notify_all()
            lane_threads = [lane.thread for lane in self._lanes]
            hedge_thread = self._hedge_thread
        for lt in lane_threads:
            if lt is not None:
                lt.join(timeout)
        if hedge_thread is not None:
            hedge_thread.join(timeout)
        leftovers: list[list] = []
        with self._lock:
            seen_tickets: set = set()
            for lane in self._lanes:
                while lane.queue:
                    tk = lane.queue.popleft()
                    lane.queued_rows -= tk.rows
                    # a hedged ticket can sit in two queues; drain its
                    # rows once, and skip tickets a dispatch already won
                    if tk in seen_tickets or tk.winner is not None:
                        continue
                    seen_tickets.add(tk)
                    leftovers.extend(row for _k, row in tk.batch)
            self._tickets.clear()
            leftovers.extend(self._pending.values())
            self._pending.clear()
            self._pending_trace.clear()
            self._pending_origin.clear()
        self._fail_rows(leftovers, RuntimeError(
            "verifier scheduler closed with unresolved futures"))

    def stats(self) -> dict:
        """Snapshot of scheduler counters (tests and the benchmark
        read deltas here instead of the process-global registry).  The
        flat keys are scheduler-wide aggregates — exactly the pre-mesh
        surface — plus ``lanes`` and a ``devices`` list of per-lane
        breakdowns (queue depth, in-flight rows, breaker state, rows /
        batches / diverts / occupancy per device)."""
        with self._lock:
            out = dict(self._stats)
            for pair in ("window_submits", "window_rows"):
                out[pair] = out[pair + "_consensus"] + out[pair + "_bulk"]
            out["cached_entries"] = len(self._cache)
            out["pending"] = len(self._pending)
            out["breaker"] = ("open" if any(
                lane.breaker == "open" for lane in self._lanes)
                else "closed")
            out["lanes"] = len(self._lanes)
            out["pipeline_overlap_ratio"] = (
                round(out["pipeline_overlapped"]
                      / out["pipeline_windows"], 4)
                if out["pipeline_windows"] else 0.0)
            devices = []
            for lane in self._lanes:
                d = {"device": lane.index,
                     "queue_depth": len(lane.queue),
                     "max_queue_depth": lane.max_queue_depth,
                     "inflight_rows": lane.inflight_rows,
                     "breaker": lane.breaker}
                d.update(lane.stats)
                d["occupancy"] = (
                    round(lane.stats["rows"] / lane.stats["bucket_rows"], 4)
                    if lane.stats["bucket_rows"] else None)
                d["pipeline_overlap_ratio"] = (
                    round(lane.stats["pipeline_overlapped"]
                          / lane.stats["pipeline_windows"], 4)
                    if lane.stats["pipeline_windows"] else 0.0)
                devices.append(d)
            out["devices"] = devices
            out["flight_windows"] = self._flight_seq
            out["flight_capacity"] = self._flights.maxlen
            out["window_ms"] = round(self._window_s * 1e3, 4)
            from eges_tpu.utils.metrics import percentile
            class_wait = {}
            for klass in sorted(self._class_waits):
                vals = sorted(self._class_waits[klass])
                class_wait[klass] = {
                    "count": len(vals),
                    "p50_ms": round(percentile(vals, 50.0), 3),
                    "p99_ms": round(percentile(vals, 99.0), 3),
                }
            out["class_wait_ms"] = class_wait
        return out

    def flights(self, limit: int = 0) -> list[dict]:
        """Flight-recorder entries, oldest first (the ring keeps the
        newest ``flight_ring`` windows — default 4096, what
        ``thw_flight`` hands out at most — and evictions count into
        ``stats()["flight_dropped"]`` / ``verifier.flight_dropped``);
        ``limit`` keeps only the newest N.  Each entry is one window's
        lifecycle: phase timestamps (``t_submit``/``t_flush``/
        ``t_begin``/``t_dispatch``/``t_collect``/``t_done``), phase
        durations (``wait_ms`` and its two halves ``flush_ms``, the
        oldest row's entry to the dispatcher's pop, and
        ``lane_wait_ms``, the pop to the stage's begin; ``stage_ms``,
        ``compute_ms``, ``resolve_ms``: from the device's answer to the
        window's last future set, the recording included) and
        lane/device attribution."""
        with self._lock:
            evs = self._newest_flights(limit) if limit and limit > 0 \
                else self._flights
            # copied under the lock: a window that is resolving writes
            # its ``resolve_ms`` under it
            return [dict(f) for f in evs]

    # -- internals --------------------------------------------------------

    # flights the hedge thresholds are derived from (the ring's whole
    # length while it was 256 long)
    _HEDGE_RECENT = 256

    def _newest_flights(self, n: int) -> list[dict]:
        """The newest ``n`` flight entries, oldest first, without
        copying the whole ring.  Caller holds ``self._lock``."""
        out = list(itertools.islice(reversed(self._flights), n))
        out.reverse()
        return out

    @staticmethod
    def _fail_rows(rows, exc: BaseException) -> None:
        """Every holder of the pending ``rows`` that has no answer yet
        takes ``exc`` as its row's value (a row is written once: one
        that a batch answered meanwhile keeps its answer), so no caller
        hangs on a window that will never run.  The synchronous facades
        recover such a row on the host; :meth:`submit`'s future raises
        it."""
        for row in rows:
            for win, idx in row[0]:
                win._set_rows((idx,), (exc,))

    def _ensure_thread(self) -> None:
        # caller holds self._lock
        if self._thread is None or not self._thread.is_alive():
            self._admission_done = False
            self._thread = threading.Thread(
                target=self._dispatch_loop, name="verifier-scheduler",
                daemon=True)
            self._thread.start()

    def _ensure_lane_thread(self, lane: _DeviceLane) -> None:
        # caller holds self._lock; lane workers start lazily on first
        # placement so single-lane schedulers never spawn them
        if lane.thread is None or not lane.thread.is_alive():
            lane.thread = threading.Thread(
                target=self._lane_loop, args=(lane,),
                name=f"verifier-lane-{lane.index}", daemon=True)
            lane.thread.start()

    def _cache_put_many(self, keys, addrs) -> None:
        """A window's results into the LRU in row order, the overflow
        evicted once: what is left is what one put a row leaves.
        Caller holds ``self._lock``."""
        cache = self._cache
        for key, addr in zip(keys, addrs):
            cache[key] = addr
            cache.move_to_end(key)
        while len(cache) > self.cache_size:
            cache.popitem(last=False)

    def _host_recover_rows(self, keys) -> list:
        """:func:`host_recover_rows`: the divert target of a window
        served on the host by rule (:meth:`_host_served`) or because its
        lane's device died, and of a row after close."""
        return host_recover_rows(keys)

    def _host_recover(self, key: tuple):
        """One row through :meth:`_host_recover_rows`."""
        return self._host_recover_rows((key,))[0]

    def _target_pad(self, lane: _DeviceLane):
        """The function by which ``lane``'s target pads a window's rows
        to a bucket; None for a target that reports none (the native
        verifiers, a test's stub)."""
        return (getattr(lane.target, "_pad", None)
                or getattr(self._verifier, "_pad", None))

    def _host_served(self, lane: _DeviceLane, batch) -> bool:
        """Whether the flushed window ``batch`` is answered on the host
        by rule, healthy device or not.  A function of what the window
        and its target are, nothing else: its rows, its class (any row
        of it entered as ``consensus``) and the bucket ``lane``'s target
        would pad it to.  One row: always (a padded 1-row dispatch costs
        more than one native recover).  More: a consensus-class window
        whose bucket is at least ``HOST_WINDOW_RATIO`` times its rows,
        where the native library is there to recover them in one call
        (the pure-Python model is milliseconds a row).  Bulk windows are
        throughput's, and nobody's quorum waits on them: they keep the
        device."""
        rows = len(batch)
        if rows == 1:
            return True
        pad = self._target_pad(lane)
        if pad is None or rows * HOST_WINDOW_RATIO > pad(rows):
            return False
        if not any(row[2] == "consensus" for _k, row in batch):
            return False
        from eges_tpu.crypto import native
        return native.available()

    def _dispatch_loop(self) -> None:
        """Wrapper keeping the strand-no-row invariant: if the flush
        loop itself dies on an unexpected error, every queued row is
        failed with that error instead of hanging its caller forever
        (``_ensure_thread`` restarts a thread on the next entry).

        The dispatcher starts at the first row that has to be computed:
        after the imports, the warm-up and whatever the caller built,
        before any window.  That is where the process settles its heap
        (``utils/heap.py``: once a process, no lock of ours held)."""
        heap.settle()
        try:
            self._dispatch_forever()
        except BaseException as exc:
            with self._lock:
                leftovers = list(self._pending.values())
                self._pending.clear()
            self._fail_rows(leftovers, exc)
            raise
        finally:
            with self._lock:
                # lane workers drain-and-exit only once the admission
                # front can place no further windows
                self._admission_done = True
                self._lock.notify_all()

    def _dispatch_forever(self) -> None:  # hot-path-entry
        """Background flush loop: wait for work, coalesce inside the
        micro-window, place/dispatch ONE window, repeat.  Exits only
        once closed AND drained."""
        while True:
            with self._lock:
                while not self._pending and not self._closed:
                    self._lock.wait()
                if not self._pending and self._closed:
                    return
                # coalescing window: more submitters may land until the
                # bucket fills (max_batch), a sync caller kicks, close
                # drains, or the deadline measured from the OLDEST entry
                # expires
                while (len(self._pending) < self.max_batch
                        and not self._kick and not self._closed
                        and self._pending):
                    oldest = next(iter(self._pending.values()))[1]
                    # analysis: allow-determinism(window-expiry wait is the real-time contract; chaos batch membership is pinned by max_batch kicks)
                    left = self._window_s - (time.monotonic() - oldest)
                    if left <= 0:
                        break
                    self._lock.wait(left)
                if not self._pending:
                    continue
                # "close" outranks "kick": close() raises the kick flag
                # to wake the window wait, and the shutdown drain must
                # be journaled as the documented flush_close step
                limit = self.max_batch
                reason = ("full" if len(self._pending) >= limit
                          else "close" if self._closed
                          else "kick" if self._kick else "deadline")
                self._stats["flush_" + reason] += 1
                if len(self._pending) > limit:
                    # overfull window: consensus-class rows outrank bulk
                    # for the seats this flush has (within a class,
                    # arrival order is preserved)
                    keys = [k for k, row in self._pending.items()
                            if row[2] == "consensus"][:limit]
                    if len(keys) < limit:
                        taken = set(keys)
                        keys += [k for k in self._pending
                                 if k not in taken][:limit - len(keys)]
                else:
                    keys = list(self._pending)
                batch = [(k, self._pending.pop(k)) for k in keys]
                if not self._pending:
                    self._kick = False
                # flight-recorder stamp: the window leaves ``_pending``;
                # what it waited before is coalescing or this thread's
                # wake-up, what it waits after is a lane's
                # analysis: allow-determinism(flight recorder timestamps are wall-clock by design and never journaled)
                t_flush = time.monotonic()
            if ((len(self._lanes) > 1 or self._pipelined)
                    and not self._host_served(self._lanes[0], batch)):
                # mesh windows go to the per-device lanes; single-lane
                # pipeline-capable targets ALSO route through the lane
                # worker, whose begin/finish split overlaps consecutive
                # windows (inline dispatch can't — it must block)
                with tracing.DEFAULT.span("sched.place", rows=len(batch)):
                    self._place(batch, reason, t_flush)
                continue
            try:
                # single-lane windows, and those the host answers by
                # rule (one row, a small consensus window), dispatch
                # inline on this thread: the pre-mesh behavior, no lane
                # worker, nothing waits behind a lane's window in flight
                self._run_batch(self._lanes[0], batch, reason, t_flush)
            # the batch's futures were already resolved or failed inside
            # _run_batch's finally; the loop survives to the next window
            # analysis: allow-swallow(futures already resolved/failed in _run_batch finally)
            except Exception:
                pass

    # -- mesh placement ---------------------------------------------------

    def _place(self, batch, reason: str, t_flush: float) -> None:
        """Place one flushed window onto the device lanes (its chunks
        share ``t_flush``, the moment the dispatcher popped it).

        A window at most ``chunk_cap = max(min_split, max_batch/lanes)``
        rows fills the single least-loaded lane; a larger one splits
        into contiguous near-equal chunks (each >= ``min_split`` rows)
        placed on DISTINCT lanes in ascending load order, so a
        saturating window reaches every device at once.  Equal-load
        ties rotate round-robin — an idle mesh still spreads
        back-to-back windows instead of pinning device 0.

        A window carrying any consensus-class row is placed at the HEAD
        of its lane's queue (placement preemption): queued bulk
        tx-ingest windows wait, already-dispatched ones are not
        interrupted.
        """
        from eges_tpu.utils.metrics import DEFAULT as metrics

        rows = len(batch)
        n_chunks = 1
        if rows > self._chunk_cap:
            n_chunks = min(len(self._lanes), -(-rows // self._chunk_cap))
            n_chunks = min(n_chunks, max(1, rows // self.min_split))
        size = -(-rows // n_chunks)
        chunks = [batch[i:i + size] for i in range(0, rows, size)]
        klass = ("consensus" if any(row[2] == "consensus"
                                    for _k, row in batch) else "bulk")
        # queue depths are captured under the lock and emitted after it:
        # the metrics registry takes its own lock, and nesting it inside
        # the scheduler condition would order-couple the two on every
        # window placement (fail-under-lock)
        depth_updates: list[tuple[int, int]] = []
        with self._lock:
            order = sorted(
                self._lanes,
                key=lambda L: (L.load(),
                               (L.index - self._rr) % len(self._lanes)))
            self._rr = (self._rr + 1) % len(self._lanes)
            if len(chunks) > 1:
                self._stats["window_splits"] += 1
            for chunk, lane in zip(chunks, order):
                tk = _WindowTicket(chunk, reason, klass, lane.index,
                                   t_flush)
                if klass == "consensus":
                    lane.queue.appendleft(tk)
                else:
                    lane.queue.append(tk)
                self._tickets.add(tk)
                lane.queued_rows += tk.rows
                lane.max_queue_depth = max(lane.max_queue_depth,
                                           len(lane.queue))
                depth_updates.append((lane.index, len(lane.queue)))
                self._ensure_lane_thread(lane)
            if self._hedge_on:
                self._ensure_hedge_thread()
            self._lock.notify_all()
        if len(chunks) > 1:
            metrics.counter("verifier.mesh_window_splits").inc()
        for index, depth in depth_updates:
            metrics.gauge(
                f"verifier.mesh_queue_depth;device={index}").set(depth)

    def _lane_loop(self, lane: _DeviceLane) -> None:  # hot-path-entry
        """One device lane's worker: drain the lane queue FIFO; on an
        unexpected loop death fail THIS lane's queued futures — other
        lanes keep serving (straggler isolation).

        On a pipeline-capable target the worker is double-buffered: it
        holds ONE collected-later window in ``pending`` and, when the
        queue has a successor, begins (fills + uploads + dispatches)
        that successor BEFORE blocking on ``pending``'s collect — so
        window k+1's H2D stages while window k computes and drains.
        Windows still finish strictly FIFO, so cache inserts and
        journal events keep their queue order.
        """
        from eges_tpu.utils.metrics import DEFAULT as metrics
        pipelined = callable(getattr(lane.target, "stage_recover", None))
        pending: _PendingWindow | None = None
        nxt_p: _PendingWindow | None = None
        try:
            while True:
                with self._lock:
                    while not lane.queue and pending is None and not (
                            self._closed and self._admission_done):
                        self._lock.wait()
                    if not lane.queue and pending is None:
                        return  # closed, admission drained, queue empty
                    nxt = None
                    depth = None
                    cancelled = False
                    if lane.queue:
                        tk = lane.queue.popleft()
                        lane.queued_rows -= tk.rows
                        depth = len(lane.queue)
                        if tk.winner is not None:
                            # the hedge raced us and its sibling dispatch
                            # already recorded this window — drop the
                            # loser before it touches the device (the
                            # "cancelled" outcome; a loser that already
                            # started finishes as "wasted" instead)
                            self._stats["hedge_cancelled"] += 1
                            self._tickets.discard(tk)
                            cancelled = True
                        else:
                            nxt = tk
                            lane.inflight_rows += tk.rows
                if depth is not None:
                    # emitted after release: the gauge takes the metrics
                    # registry lock (fail-under-lock)
                    metrics.gauge(
                        f"verifier.mesh_queue_depth;device={lane.index}") \
                        .set(depth)
                if cancelled:
                    metrics.counter("verifier.hedge_cancelled").inc()
                nxt_p: _PendingWindow | None = None
                if nxt is not None:
                    if pipelined:
                        with tracing.DEFAULT.span("sched.stage",
                                                  rows=nxt.rows,
                                                  device=lane.index):
                            nxt_p = self._begin_batch(lane, nxt.batch,
                                                      nxt.reason,
                                                      nxt.t_flush,
                                                      ticket=nxt)
                        if (pending is not None and nxt_p.staged is not None
                                and nxt_p.failure is None):
                            # this begin's H2D ran while the previous
                            # window was still on the device — the
                            # overlap the ratio metric reports
                            with self._lock:
                                self._stats["pipeline_overlapped"] += 1
                                lane.stats["pipeline_overlapped"] += 1
                    else:
                        try:
                            self._run_batch(lane, nxt.batch, nxt.reason,
                                            nxt.t_flush, ticket=nxt)
                        # analysis: allow-swallow(futures already resolved/failed in _run_batch finally; the lane survives to its next window)
                        except Exception:
                            pass
                        finally:
                            with self._lock:
                                lane.inflight_rows -= nxt.rows
                if pending is not None:
                    self._finish_lane_window(lane, pending)
                    pending = None
                if nxt_p is not None:
                    if (nxt_p.staged is not None and not nxt_p.computed
                            and nxt_p.failure is None):
                        pending = nxt_p
                    else:
                        # host-served (by rule or divert) / failed windows
                        # have nothing on the device — finish them now
                        self._finish_lane_window(lane, nxt_p)
        except BaseException as exc:
            with self._lock:
                leftovers = list(lane.queue)
                lane.queue.clear()
                lane.queued_rows = 0
                for tk in leftovers:
                    self._tickets.discard(tk)
            unfinished = []
            if pending is not None and not pending.finished:
                unfinished.append(pending)
            if (nxt_p is not None and nxt_p is not pending
                    and not nxt_p.finished):
                unfinished.append(nxt_p)
            for p in unfinished:
                with self._lock:
                    lane.inflight_rows -= p.rows
                self._fail_rows((row for _k, row in p.batch), exc)
            for tk in leftovers:
                # a hedged ticket's sibling dispatch may still win; a
                # row is written once, so the race is harmless either way
                self._fail_rows((row for _k, row in tk.batch), exc)
            raise

    def _finish_lane_window(self, lane: _DeviceLane,
                            p: _PendingWindow) -> None:
        """Collect + record + resolve one lane window, releasing its
        in-flight rows whatever happens."""
        try:
            self._finish_batch(lane, p)
        # analysis: allow-swallow(futures already resolved/failed in _finish_batch finally; the lane survives to its next window)
        except Exception:
            pass
        finally:
            with self._lock:
                lane.inflight_rows -= p.rows

    # -- breaker (per lane) -----------------------------------------------

    def _breaker_admits(self, lane: _DeviceLane) -> tuple[bool, bool]:
        """(use_device, probing): closed -> dispatch normally; open ->
        host-divert until the cooldown elapses, then admit ONE half-open
        probe window."""
        from eges_tpu.utils.metrics import DEFAULT as metrics
        with self._lock:
            if lane.breaker == "closed":
                return True, False
            if self.breaker_clock() >= lane.breaker_until:
                self._stats["breaker_probes"] += 1
                lane.stats["breaker_probes"] += 1
                probe = True
            else:
                return False, False
        metrics.counter("verifier.breaker_probes").inc()
        return True, probe

    def _breaker_trip(self, lane: _DeviceLane, probing: bool) -> None:
        from eges_tpu.utils.metrics import DEFAULT as metrics
        with self._lock:
            self._stats["device_errors"] += 1
            self._stats["breaker_trips"] += 1
            lane.stats["device_errors"] += 1
            lane.stats["breaker_trips"] += 1
            lane.breaker = "open"
            lane.breaker_until = self.breaker_clock() \
                + self.breaker_cooldown_s
        metrics.counter("verifier.device_errors").inc()
        metrics.counter("verifier.breaker_trips").inc()
        metrics.gauge("verifier.breaker_state").set(1)
        journal = self.journal
        if journal is not None:
            journal.record("fault_breaker", state="open",
                           probe=bool(probing), device=lane.index,
                           cooldown_s=self.breaker_cooldown_s)

    def _breaker_close(self, lane: _DeviceLane) -> None:
        from eges_tpu.utils.metrics import DEFAULT as metrics
        with self._lock:
            lane.breaker = "closed"
            any_open = any(x.breaker == "open" for x in self._lanes)
        metrics.gauge("verifier.breaker_state").set(1 if any_open else 0)
        journal = self.journal
        if journal is not None:
            journal.record("fault_breaker", state="closed",
                           device=lane.index)

    # -- window execution -------------------------------------------------

    def _run_batch(self, lane: _DeviceLane, batch, reason: str,
                   t_flush: float,
                   ticket: "_WindowTicket | None" = None) -> None:
        """Dispatch one coalesced window (or mesh chunk) on ``lane``,
        OUTSIDE the scheduler lock (the device call is the long pole;
        submitters keep queueing into the next window meanwhile).  The
        inline composition of the split-phase halves: begin (fill +
        dispatch) then finish (collect + record + resolve) with no
        overlap — the pre-pipeline behavior."""
        with tracing.DEFAULT.span("sched.stage", rows=len(batch),
                                  device=lane.index):
            p = self._begin_batch(lane, batch, reason, t_flush, ticket)
        self._finish_batch(lane, p)

    def _begin_batch(self, lane: _DeviceLane, batch, reason: str,
                     t_flush: float,
                     ticket: "_WindowTicket | None" = None) -> _PendingWindow:
        """Phase 1 of one window: host-by-rule/breaker divert decisions,
        numpy fill, and the device dispatch.  ``t_flush`` is when the
        dispatcher popped the window (a chunk's: its ticket's).  On a pipeline-capable
        target the dispatch is split-phase (stage H2D + async commit,
        left in ``staged`` for ``_finish_batch`` to collect); otherwise
        the device call runs to completion here.  NEVER raises — any
        error lands in ``failure`` so the caller always gets a window
        to finish (and the futures always resolve there)."""
        p = _PendingWindow()
        p.batch = batch
        p.keys = [k for k, _ in batch]
        p.reason = reason
        p.ticket = ticket
        p.rows = len(batch)
        p.results = [None] * p.rows
        p.staged = None
        p.probing = False
        p.diverted = False
        p.computed = False
        p.failure = None
        p.finished = False
        p.t_dispatch = None
        p.t_collect = None
        p.flight = None
        p.hosted = False
        # analysis: allow-determinism(batch latency instrumentation; dt/waited_ms are volatile-stripped)
        p.t0 = time.monotonic()
        p.t_flush = t_flush
        try:
            if self._host_served(lane, batch):
                # host divert by rule: a padded 1-row device dispatch
                # costs more than one native recover, and so does a few
                # rows' round trip that a quorum waits for — keep the
                # device for real batches and
                # verifier.singleton_batches at zero.  The breaker is
                # not consulted: nothing touches the device
                p.hosted = True
                p.results = self._host_recover_rows(p.keys)
                with self._lock:
                    self._stats["host_diverted"] += 1
                    self._stats["host_diverted_rows"] += p.rows
                    lane.stats["host_diverted"] += 1
                p.computed = True
                return p
            use_device, p.probing = self._breaker_admits(lane)
            if not use_device:
                # breaker open: this lane's device is presumed dead
                # — the whole window takes the host recover path so
                # consensus keeps committing (other lanes are
                # unaffected: the breaker is lane-scoped)
                p.results = self._host_recover_rows(p.keys)
                p.diverted = True
                with self._lock:
                    self._stats["breaker_diverted"] += p.rows
                    lane.stats["breaker_diverted"] += p.rows
                p.computed = True
                return p
            hashes, sigs = _key_arrays(p.keys)
            stage = getattr(lane.target, "stage_recover", None)
            try:
                hook = self.failure_hook
                if hook is not None:
                    hook(p.rows)
                if callable(stage):
                    # split-phase: fill + H2D + async device dispatch
                    # now; the blocking collect happens in
                    # _finish_batch — possibly after the NEXT window's
                    # stage (that concurrency is the pipeline)
                    p.staged = lane.target.commit_recover(
                        stage(sigs, hashes))
                    with self._lock:
                        self._stats["pipeline_windows"] += 1
                        lane.stats["pipeline_windows"] += 1
                else:
                    addrs, ok = lane.target.recover_addresses(
                        sigs, hashes)
                    p.results = _row_results(addrs, ok)
                    if p.probing:
                        self._breaker_close(lane)
                    p.computed = True
            # analysis: allow-swallow(a device exception diverts
            # exactly this window to the host model — the queued
            # futures still resolve correctly — and trips this
            # lane's circuit breaker for the windows after it)
            except Exception:
                self._breaker_trip(lane, p.probing)
                p.results = self._host_recover_rows(p.keys)
                p.diverted = True
                p.computed = True
        except BaseException as exc:
            p.failure = exc
        if p.t_dispatch is None:
            # flight-recorder stamp: dispatch phase done (device call
            # issued, inline compute complete, or host divert served)
            # analysis: allow-determinism(flight recorder timestamps are wall-clock by design and never journaled)
            p.t_dispatch = time.monotonic()
        return p

    def _finish_batch(self, lane: _DeviceLane, p: _PendingWindow) -> None:
        """Phase 2 of one window: collect the staged device result (if
        split-phase, span ``sched.collect``), then under ``sched.resolve``
        turn it into row results, insert them into the cache, record
        the window and, always, answer its holders
        (:meth:`_resolve_batch`).  Re-raises the window's failure after
        resolution, matching the old ``_run_batch`` contract."""
        got = None
        if p.failure is None and p.staged is not None and not p.computed:
            try:
                with tracing.DEFAULT.span("sched.collect", rows=p.rows,
                                          device=lane.index):
                    got = lane.target.collect_recover(p.staged)
            # analysis: allow-swallow(a device exception surfacing at
            # collect diverts exactly this window to the host model and
            # trips the lane breaker, like a synchronous dispatch
            # failure would)
            except Exception:
                self._breaker_trip(lane, p.probing)
                p.results = self._host_recover_rows(p.keys)
                p.diverted = True
                p.computed = True
            except BaseException as exc:
                p.failure = exc
            # flight-recorder stamp: the device's answer is on the host
            # analysis: allow-determinism(flight recorder timestamps are wall-clock by design and never journaled)
            p.t_collect = time.monotonic()
        with tracing.DEFAULT.span("sched.resolve", rows=p.rows,
                                  device=lane.index):
            self._resolve_batch(lane, p, got)
        if p.failure is not None:
            raise p.failure

    def _resolve_batch(self, lane: _DeviceLane, p: _PendingWindow,
                       got) -> None:
        """The tail of :meth:`_finish_batch`, in steps a WINDOW and one
        a waiting call, never several a row.  ``got`` is what
        ``collect_recover`` returned, None for a window that was
        computed (or failed) before.  In order: the results, cut from
        one copy; the hedge ticket's claim and the cache inserts, one
        lock hold (:meth:`_claim`); the recording
        (:meth:`_record_window`); the holders, one hold per waiting
        call (:meth:`_answer`); last what only the end of that can
        know, ``resolve_ms`` and ``resolve_holds``.

        The recording stands in front of the holders, and is a constant
        number of steps: the journal's events, the devstats deltas and
        ``stats()`` are protocol content to the deterministic sims,
        which must find them settled the moment an answer is out; and
        under one interpreter lock a woken caller runs only when this
        thread next blocks, whichever came first."""
        from eges_tpu.utils.metrics import DEFAULT as metrics

        try:
            if got is not None:
                p.results = _row_results(*got)
                if p.probing:
                    self._breaker_close(lane)
                p.computed = True
            if p.failure is None and p.computed and self._claim(lane, p):
                self._record_window(lane, p, len(self._lanes) > 1)
        except BaseException as exc:
            if p.failure is None:
                p.failure = exc
        finally:
            # the holders are answered even if the recording raised: a
            # blocked recover_signers caller is a wedged consensus node
            p.finished = True
            holds = self._answer(p)
        flight = p.flight
        if flight is None:
            return  # a hedge loser, or a window that died: not recorded
        # what a caller waited after the device's answer ends here, at
        # the last holder set, not at ``t_done``
        # analysis: allow-determinism(flight recorder timestamps are wall-clock by design and never journaled)
        resolve_s = time.monotonic() - flight["t_collect"]
        with self._lock:
            flight["resolve_ms"] = round(resolve_s * 1e3, 3)
            self._stats["resolve_holds"] += holds
        # the window's own stage and resolve beside the caller's
        # sched.submit / sched.await, under the same two labels: a
        # 1024-row burst's means are not mixed with a 32-row call's
        labels = _call_labels(flight["klass"], p.rows)
        tail = ";class=%s,size=%s" % (labels["class"], labels["size"])
        metrics.histogram("verifier.window_stage_seconds" + tail) \
            .observe(flight["stage_ms"] / 1e3)
        metrics.histogram("verifier.window_resolve_seconds" + tail) \
            .observe(resolve_s)

    def _claim(self, lane: _DeviceLane, p: _PendingWindow) -> bool:
        """ONE lock hold between a computed window's results and
        anything that can wake a caller: the hedge ticket's claim (the
        first dispatch to finish wins the window) and, for the winner,
        the cache inserts in row order, so a caller that has its answer
        and asks again finds it cached.  Returns whether this dispatch
        won: a loser's (bit-identical) results are discarded and it
        skips :meth:`_record_window`, which keeps cache, stats, journal,
        flights and ledger charges exactly-once per window."""
        tk = p.ticket
        won, hedge_won = True, False
        with self._lock:
            if tk is not None:
                if tk.winner is None:
                    tk.winner = lane.index
                    self._tickets.discard(tk)
                    if tk.hedged and lane.index == tk.hedge_lane:
                        self._stats["hedge_wins"] += 1
                        hedge_won = True
                else:
                    won = False  # the sibling won while we computed
                    self._stats["hedge_wasted"] += 1
            if won:
                self._cache_put_many(p.keys, p.results)
        if hedge_won:
            from eges_tpu.utils.metrics import DEFAULT as metrics
            metrics.counter("verifier.hedge_wins").inc()
        elif not won:
            from eges_tpu.utils.metrics import DEFAULT as metrics
            metrics.counter("verifier.hedge_wasted").inc()
            # a loser window burned a full padded bucket on its lane
            # for nothing — bill the waste to the device-efficiency
            # ledger at the padded size
            from eges_tpu.utils import devstats
            devstats.DEFAULT.observe_hedge_waste(
                lane.index, p.rows, self._bucket_of(lane, p))
        return won

    def _bucket_of(self, lane: _DeviceLane, p: _PendingWindow) -> int:
        """The padded rows ``p`` cost: its target's bucket, or its own
        rows where the host answered it by rule (it padded nothing)."""
        if p.hosted:
            return p.rows
        return (self._target_pad(lane) or bucket_round)(p.rows)

    def _answer(self, p: _PendingWindow) -> int:
        """Every holder of the batch gets its row's value exactly once:
        the rows of one :class:`_WindowRows` together in ONE hold, each
        holder of a dedup-shared row.  Returns the holds taken.  If the
        batch died before it had results, its holders take that error
        as their rows' value rather than a None masquerading as
        "invalid signature" (:meth:`submit`'s future raises it).  A
        hedge loser comes through here too: the winner has answered
        everything, and a row is written once, so it changes nothing."""
        failure = None if p.computed else (p.failure or RuntimeError(
            "verifier batch dispatch failed"))
        windows: dict = {}
        for (_, row), r in zip(p.batch, p.results):
            for win, idx in row[0]:
                rows = windows.get(win)
                if rows is None:
                    rows = windows[win] = ([], [])
                rows[0].append(idx)
                rows[1].append(r if failure is None else failure)
        for win, (idxs, values) in windows.items():
            win._set_rows(idxs, values)
        return len(windows)

    def _record_window(self, lane: _DeviceLane, p: _PendingWindow,
                       mesh: bool) -> None:
        """Stats + flight + metrics + journal for one computed window —
        the bookkeeping tail shared by the inline and pipelined paths,
        inside the caller's ``sched.resolve`` span (errors here
        propagate to ``_resolve_batch``, which still answers the
        holders in its ``finally``).  Every plane is written once a
        WINDOW: the rows that entered together share one submit time
        and one class, so the queue-wait planes, which count ROWS, take
        one weighted observation per such group."""
        from eges_tpu.utils.metrics import DEFAULT as metrics

        batch, keys, rows = p.batch, p.keys, p.rows
        # (t_submit, class) -> rows: one pass over the batch, behind
        # the oldest entry, the window's class and the queue waits
        groups = Counter([(row[1], row[2]) for _, row in batch])
        # analysis: allow-determinism(batch latency instrumentation; waited_ms is volatile-stripped)
        done = time.monotonic()
        bucket = self._bucket_of(lane, p)
        oldest = min(t for t, _ in groups)
        waited = p.t0 - oldest
        tk = p.ticket
        klass = ("consensus" if any(k == "consensus"
                                    for _, k in groups) else "bulk")
        # one flight-recorder entry per computed window: lifecycle phase
        # boundaries + lane attribution (the thw_flight RPC surface)
        t_dispatch = p.t_dispatch if p.t_dispatch is not None else done
        t_collect = p.t_collect if p.t_collect is not None else t_dispatch
        flight = {
            "device": lane.index, "rows": rows, "bucket": bucket,
            "reason": p.reason, "diverted": bool(p.diverted),
            "probing": bool(p.probing),
            "pipelined": p.staged is not None,
            "t_submit": round(oldest, 6), "t_flush": round(p.t_flush, 6),
            "t_begin": round(p.t0, 6),
            "t_dispatch": round(t_dispatch, 6),
            "t_collect": round(t_collect, 6), "t_done": round(done, 6),
            "wait_ms": round(waited * 1e3, 3),
            # the wait's two halves: to the dispatcher's pop (coalescing
            # up to the deadline or, for a kicked call, its wake-up),
            # then to the stage's begin (sched.place, the lane's queue,
            # the lane worker's wake-up)
            "flush_ms": round((p.t_flush - oldest) * 1e3, 3),
            "lane_wait_ms": round((p.t0 - p.t_flush) * 1e3, 3),
            "stage_ms": round((t_dispatch - p.t0) * 1e3, 3),
            "compute_ms": round((t_collect - t_dispatch) * 1e3, 3),
            # results, the ticket claim and the cache inserts so far;
            # once the recording below and the holders are through,
            # _resolve_batch puts the whole of it here
            "resolve_ms": round((done - t_collect) * 1e3, 3),
            "total_ms": round((done - oldest) * 1e3, 3),
            "klass": klass,
            "hedged": bool(tk is not None and tk.hedged),
            "hedge_win": bool(tk is not None and tk.hedged
                              and lane.index == tk.hedge_lane),
            "traces": [],
        }
        flight_evicts = False
        with self._lock:
            # blk/trace linkage: distinct submitter trace ids riding this
            # window (txpool ingest spans, quorum verifies) — popped here
            # so the map never outlives its window
            # (a map that holds nothing, as when no submitter has a
            # trace or a ledger bound, is not probed a row)
            traces = sorted({t for t in (self._pending_trace.pop(k, None)
                                         for k in keys) if t}) \
                if self._pending_trace else []
            # ingress provenance: rows per captured (ledger, origin) —
            # tallied under the lock, charged after release (the ledger
            # emits metrics; fail-under-lock hygiene)
            origin_rows: dict[tuple, int] = {}
            if self._pending_origin:
                for k in keys:
                    rec = self._pending_origin.pop(k, None)
                    if rec is not None:
                        origin_rows[rec] = origin_rows.get(rec, 0) + 1
            cache_rows = self._cache_rows_pending
            self._cache_rows_pending = 0
            dedup_rows = self._dedup_rows_pending
            self._dedup_rows_pending = 0
            self._stats["batches"] += 1
            self._stats["rows"] += rows
            self._stats["bucket_rows"] += bucket
            lane.stats["batches"] += 1
            lane.stats["rows"] += rows
            lane.stats["bucket_rows"] += bucket
            if p.diverted and mesh:
                self._stats["straggler_diverts"] += 1
                lane.stats["straggler_diverts"] += 1
            windows = self._stats["pipeline_windows"]
            overlapped = self._stats["pipeline_overlapped"]
            flight["traces"] = traces[:4]
            flight["trace_count"] = len(traces)
            # cache-served rows since the previous window: the warm-path
            # volume that never forms a window of its own (the
            # under-count bug this field closes)
            flight["cache_rows"] = cache_rows
            # in-flight-deduped rows merged into this window's rows —
            # the free-work companion the goodput decomposition renders
            flight["dedup_rows"] = dedup_rows
            flight["window"] = self._flight_seq
            self._flight_seq += 1
            if (self._flights.maxlen is not None
                    and len(self._flights) >= self._flights.maxlen):
                # the ring is full: this append evicts the oldest entry
                # — the silent-loss signal the flight_dropped counter
                # and observatory surface
                self._stats["flight_dropped"] += 1
                flight_evicts = True
            self._flights.append(flight)
            p.flight = flight
            # per-class queue-wait samples behind stats()'s percentiles
            for (t_submit, k), n in groups.items():
                self._class_waits[k].extend(
                    itertools.repeat((p.t0 - t_submit) * 1e3, n))
        # per-origin window cost: each captured origin gets its row
        # count plus its row-share of the window's wall-clock interior,
        # booked as host-ms when the rows were host-served (by rule,
        # or breaker/straggler divert) and device-ms otherwise
        if origin_rows:
            win_ms = (done - p.t0) * 1e3
            host_served = p.diverted or p.hosted
            for (led, origin), n in origin_rows.items():
                ms = win_ms * (n / rows)
                led.charge(origin, rows=n,
                           host_ms=ms if host_served else 0.0,
                           device_ms=0.0 if host_served else ms)
        metrics.counter("verifier.flight_windows").inc()
        if flight_evicts:
            metrics.counter("verifier.flight_dropped").inc()
        # per-class queue-wait: the priority-preemption deliverable is
        # visible as a class-labeled histogram split
        wait_all = metrics.histogram("verifier.sched_queue_wait_seconds")
        wait_of = {k: metrics.histogram(
            "verifier.sched_queue_wait_seconds;class=%s" % k)
            for k in {k for _, k in groups}}
        for (t_submit, k), n in groups.items():
            wait_all.observe(p.t0 - t_submit, n)
            wait_of[k].observe(p.t0 - t_submit, n)
        metrics.histogram("verifier.sched_batch_rows").observe(rows)
        metrics.histogram("verifier.sched_occupancy") \
            .observe(rows / bucket)
        if windows:
            metrics.gauge("verifier.pipeline_overlap_ratio") \
                .set(round(overlapped / windows, 4))
        if mesh:
            metrics.counter(
                f"verifier.mesh_rows;device={lane.index}").inc(rows)
            metrics.histogram(
                f"verifier.mesh_occupancy;device={lane.index}") \
                .observe(rows / bucket)
            if p.diverted:
                metrics.counter(
                    f"verifier.mesh_straggler_diverts"
                    f";device={lane.index}").inc()
        # device-efficiency ledger (utils/devstats.py): deterministic
        # count deltas only — the goodput numerator/denominator this
        # window contributed, journaled on the next devstats tick.
        # Host-served windows (by rule, or breaker/straggler divert)
        # padded no device bucket, so they land in the rescue column.
        from eges_tpu.utils import devstats
        devstats.DEFAULT.observe_window(
            lane.index, rows, bucket,
            cache_rows=cache_rows, dedup_rows=dedup_rows,
            diverted=bool(p.diverted or p.hosted),
            hedged=flight["hedged"])
        journal = self.journal
        if journal is not None:
            journal.record("verifier_flush", rows=rows, reason=p.reason,
                           occupancy=round(rows / bucket, 4),
                           waited_ms=round(waited * 1e3, 3))
            # commit-anatomy verify-window interior: the wall-clock
            # wait/stage/compute split plus lane and trace linkage, so
            # the critical-path assembler can attribute the admission
            # leg to scheduler queueing vs device time.  The wall-clock
            # attrs (and the race-placed lane) are volatile-stripped by
            # the chaos canonical dump; rows/reason/diverted are pinned
            # by kick-driven batching and stay in it.
            journal.record("commit_anatomy", stage="verify_window",
                           rows=rows, reason=p.reason,
                           diverted=bool(p.diverted), lane=lane.index,
                           wait_ms=round(waited * 1e3, 3),
                           stage_ms=flight["stage_ms"],
                           compute_ms=flight["compute_ms"],
                           traces=len(traces))
            if mesh:
                journal.record("verifier_mesh_dispatch",
                               device=lane.index, rows=rows,
                               occupancy=round(rows / bucket, 4),
                               diverted=p.diverted,
                               queue_wait_ms=round(waited * 1e3, 3))

    # -- hedged re-dispatch (straggler speculation) -----------------------

    def _ensure_hedge_thread(self) -> None:
        # caller holds self._lock; the monitor starts lazily on the
        # first mesh placement so single-lane schedulers (and meshes
        # with hedging disabled) never spawn it
        if self._hedge_thread is None or not self._hedge_thread.is_alive():
            self._hedge_thread = threading.Thread(
                target=self._hedge_loop, name="verifier-hedge",
                daemon=True)
            self._hedge_thread.start()

    def _lane_threshold_ms(self, lane_index: int) -> float:
        """Straggler threshold for one lane: the median window total
        over this lane's recent flights × ``HEDGE_FACTOR`` — the
        all-lane median until the lane has ``hedge_min_windows`` of its
        own history — floored at ``hedge_floor_ms`` so an idle mesh
        never hedges on noise.  Caller holds ``self._lock``."""
        from eges_tpu.utils.metrics import percentile

        recent = self._newest_flights(self._HEDGE_RECENT)
        lane_tot = sorted(f["total_ms"] for f in recent
                          if f["device"] == lane_index)
        if len(lane_tot) >= self.hedge_min_windows:
            base = percentile(lane_tot, 50.0)
        else:
            all_tot = sorted(f["total_ms"] for f in recent)
            base = percentile(all_tot, 50.0) if all_tot else 0.0
        return max(self.hedge_floor_ms, HEDGE_FACTOR * base)

    def _hedge_scan(self) -> list:
        """One straggler-monitor pass (caller holds ``self._lock``):
        every live, un-hedged ticket whose wall-clock age exceeds its
        lane's flight-derived threshold is speculatively re-placed on
        the least-loaded OTHER lane with a closed breaker.  Returns the
        tickets hedged this pass (for post-lock metrics emission)."""
        if not self._tickets:
            return []
        # Straggler aging is wall-clock by nature — a stuck lane freezes
        # the sim's virtual clock exactly when hedging must fire; hedged
        # windows journal nothing, so determinism holds.
        # analysis: allow-determinism(hedge aging; hedges journal nothing)
        now = time.monotonic()
        picks = []
        for tk in list(self._tickets):
            if tk.hedged or tk.winner is not None:
                continue
            age_ms = (now - tk.t_placed) * 1e3
            if age_ms < self._lane_threshold_ms(tk.lane):
                continue
            sibs = [L for L in self._lanes
                    if L.index != tk.lane and L.breaker == "closed"]
            if not sibs:
                continue
            sib = min(sibs, key=lambda L: (L.load(), L.index))
            tk.hedged = True
            tk.hedge_lane = sib.index
            # the duplicate rides the sibling's queue like any other
            # window (consensus class still preempts); first result
            # wins — the loser is cancelled at pop or wasted at finish
            if tk.klass == "consensus":
                sib.queue.appendleft(tk)
            else:
                sib.queue.append(tk)
            sib.queued_rows += tk.rows
            sib.max_queue_depth = max(sib.max_queue_depth,
                                      len(sib.queue))
            self._stats["hedges"] += 1
            self._ensure_lane_thread(sib)
            picks.append(tk)
        if picks:
            self._lock.notify_all()
        return picks

    def _hedge_loop(self) -> None:  # hot-path-entry
        """Straggler monitor: while any window ticket is live, poll its
        age against the lane's flight-derived threshold and re-place
        stragglers on a sibling lane.  Polling is REAL time on purpose
        (see ``_hedge_scan``): the injectable virtual clock freezes
        while a stuck window blocks the sim's clock thread, which is
        precisely when hedging has to fire.  Hedges touch stats,
        metrics and the flight ring only — never the journal — so chaos
        determinism is unaffected by when (or whether) they happen."""
        from eges_tpu.utils.metrics import DEFAULT as metrics

        while True:
            with self._lock:
                if self._closed and self._admission_done:
                    return
                if not self._tickets:
                    # nothing in flight: sleep until a placement (or
                    # close) notifies the condition
                    self._lock.wait()
                    continue
                # analysis: allow-determinism(hedge polling is real-time
                # by contract; hedged windows journal nothing)
                self._lock.wait(self._hedge_poll_s)
                picks = self._hedge_scan()
            for _tk in picks:
                metrics.counter("verifier.hedges").inc()


def scheduler_for(verifier, **kwargs) -> VerifierScheduler | None:
    """Attach (or reuse) the scheduler for a verifier object.

    The scheduler rides as an attribute on the verifier itself, so every
    component holding the same device facade — all sim-cluster nodes,
    the chain, the txpool — shares one coalescing window and one
    recovery cache (and, for mesh verifiers, one set of device lanes),
    and the pair is garbage-collected together.  That is the sharing
    inside ONE process; node processes of one host share them the second
    way, through the verify sidecar (``crypto/sidecar.py``): the
    sidecar's process holds this scheduler and each node a
    ``SidecarClient`` in its place.  ``None`` (host-fallback mode)
    passes through: those nodes keep the per-entry host path.
    """
    if verifier is None:
        return None
    if isinstance(verifier, VerifierScheduler):
        return verifier
    sched = getattr(verifier, "_eges_scheduler", None)
    if sched is None or sched.closed:
        sched = VerifierScheduler(verifier, **kwargs)
        verifier._eges_scheduler = sched
    return sched
