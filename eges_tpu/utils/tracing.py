"""Span-based tracing: ids, parent links, attributes, ring-buffer export.

Role: the distributed half of the observability layer.  The metrics
registry (``utils/metrics.py``) answers "how long does phase X take in
aggregate"; this module answers "what happened to *this* transaction" by
stitching one trace id through tx ingest -> txpool admit -> verifier
batch -> election -> chain commit, across simnet and socket transports.

Wire format: trace context rides in front of the existing gossip/direct
payloads as a fixed 28-byte header::

    MAGIC (4B, b"\\xD7TRC") | trace_id (16B) | span_id (8B)

``inject_current`` prepends it when a span is active, ``extract`` strips
it on receipt, and ``payload_of`` lets protocol muxes peek the real RLP
payload without caring whether a header is present.  Nodes that predate
this header simply never see MAGIC and pass payloads through untouched.

``Tracer.span`` is also the program's ONE timing primitive for the served
path (:data:`SPANS` names the sites).  Besides the ring entry, a live
span (a) enters a ``jax.profiler.TraceAnnotation`` for its body, so that
under a profiler session it lands in the ``.xplane.pb`` on the device
trace's clock and an idle gap of the chip can be named by the layer that
spent it (the one link to that clock); (b) reads the tracer's wall clock
on entry and on exit and observes ``span.seconds;name=<name>`` and
``span.self_seconds;name=<name>`` (duration minus what child spans on
the same thread covered) in the metrics registry, which ``thw_metrics``
exports; (c) on one span in :data:`CPU_EVERY` also reads the CPU clock
of its thread (``time.thread_time()``) on entry and on exit and observes
``span.self_cpu_seconds;name=<name>`` (what the thread RAN inside the
span, minus its children's) with that weight, so the histogram's count
times its mean estimates the CPU time of all; self wall less self CPU
is what the thread spent NOT running: the GIL, a lock, the device, a
sleep; (d) tags the thread for the sampling profiler
(``profiler.SPAN_PHASES``).  A ``TraceAnnotation`` and a CPU clock
belong to a thread: a span goes around a synchronous section, never
around an ``await``.

Why one in :data:`CPU_EVERY`: the thread's CPU clock is a system call
on every platform, 0.3 us in a plain Linux process and 5.4-6.1 us on
the sealed hosts the chips are served from, where it also moves in
steps of 10 ms (PR 38's chip runs: two reads there cost more than the
whole span did before, and one span's reading is 0 or 0.01: only the
sum over many spans means anything).  A span that is outermost on its
thread reads it by the toss of a seeded coin, whatever its name and
whatever came before (spans come in periods, a flush every few
ingests, and a count would keep step with them); a span inside one
that reads it reads it too, so a parent's self CPU is always less its
children's.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass

from eges_tpu.utils import metrics as metrics_mod
from eges_tpu.utils import profiler

MAGIC = b"\xd7TRC"
_HEADER_LEN = len(MAGIC) + 16 + 8

_UNSET = object()

# The served path's spans: name -> (label attributes, what the span
# bounds).  One span per window, call or message, never per row.  A
# label attribute's value becomes a label of the span's three
# histograms (``span.seconds``, ``span.self_seconds``: wall time;
# ``span.self_cpu_seconds``: what the thread ran), as in
# ``span.seconds;name=sched.await,class=consensus,size=burst``; every
# label has a closed vocabulary.  Other names stay allowed; they carry
# no label.  The ring entry has the span's whole wall and CPU time
# (``duration_s``, ``cpu_s``).  PERF.md section 3 copies this table.
SPANS = {
    "ingress.decode": ((), "a window of frames to columns: one native "
                           "call that holds no GIL"),
    "txpool.ingest": ((), "dedup against the known set, queueing; the one "
                          "span that begins a trace (root=True)"),
    "txpool.flush": ((), "hand the queue to the verifier, wait, admit"),
    "txpool.admit_window": ((), "nonce/balance checks and insertion of "
                                "one flushed slice"),
    "txpool.evict": ((), "commit eviction, with one tx.commit record "
                         "an ingest trace among the block's "
                         "transactions (attrs tx, txns, txs, block; "
                         "counters txpool.commit_rows, "
                         "txpool.commit_records, one inc a call)"),
    "sched.submit": (("class", "size"), "row keys, then the window's "
                                        "entry (cache probe, dedup, "
                                        "slots) under one lock hold, up "
                                        "to kick()"),
    "sched.await": (("class", "size"), "from kick() to the last result, "
                                       "as the caller feels it"),
    "sched.place": ((), "a flushed window onto the device lanes: the "
                        "split into chunks and the lane choice, under "
                        "one lock hold (mesh and pipelined targets)"),
    "sched.stage": ((), "fill, H2D, dispatch of one window; like collect "
                        "and resolve it names its lane in the attribute "
                        "``device``, which is no label"),
    "sched.collect": ((), "blocked in collect_recover: the only span "
                          "under which the device should be busy"),
    "sched.resolve": ((), "results to bytes, cache put, the recording, "
                          "futures set"),
    "sidecar.call": (("class",), "a node's synchronous call through the "
                                 "host's verify sidecar "
                                 "(crypto/sidecar.py SidecarClient): the "
                                 "frames sent, the wait, the rows no "
                                 "sidecar answered recovered on this "
                                 "host; attr rows"),
    "sidecar.recv": ((), "the sidecar's reader, one request frame: its "
                         "body off the socket (the wait for a frame to "
                         "begin is outside); attr rows"),
    "sidecar.reply": ((), "the sidecar's writer, one resolved window: "
                          "the answers to bytes and onto the socket; "
                          "attr rows"),
    "consensus.handle": (("kind",), "one gossip or direct message handled, "
                                    "under the node's lock"),
    "consensus.verify_quorum": ((), "one attempt at a quorum: every "
                                    "collected signature through the "
                                    "scheduler (consensus/quorum.py); "
                                    "attrs rows, attempt (from 1), need; "
                                    "counters consensus.quorum_attempts, "
                                    "consensus.quorum_rows, "
                                    "consensus.quorum_pruned, "
                                    "consensus.quorums, one inc a call; "
                                    "histogram consensus.quorum_seconds"),
    "consensus.cert_ok": ((), "a confirm's certificate: its supporters' "
                              "signatures through the scheduler as one "
                              "consensus-class call (consensus/quorum.py "
                              "cert_ok); attr rows"),
    "consensus.build_proposal": ((), "the elected proposer's build of "
                                     "its block (consensus/node.py "
                                     "_build_proposal): the pool's "
                                     "pending run, the preview, the "
                                     "padding, the header and the body's "
                                     "root; attrs number, txns, fakes; "
                                     "counter consensus.proposals_built"),
    "consensus.request": ((), "the validate request signed, the whole "
                              "block packed and gossiped "
                              "(_build_and_validate, _ask_for_ack); attr "
                              "bytes; counter consensus.request_bytes, "
                              "one inc(bytes) a gossiped request"),
    "consensus.seal": ((), "a certified proposal sealed (_finish_seal): "
                           "the confirm with its supporters' signatures "
                           "signed, chain.offer and its insert, the "
                           "listeners, the confirm's gossip; attrs "
                           "number, supporters"),
    "txpool.pending": ((), "the executable run a proposer drains "
                           "(core/txpool.py pending_txns): every sender's "
                           "pending transactions sorted by nonce, cut at "
                           "the state's nonce, a gap or the balance; "
                           "attrs limit, picked, senders"),
    "chain.execute_preview": ((), "a proposer's dry run of its block on "
                                  "the head state (core/chain.py): the "
                                  "senders, the apply_txn loop, the state "
                                  "root, the receipts' root, the bloom; "
                                  "attrs txns, kept, evm_calls and "
                                  "reverted (as chain.execute's); "
                                  "counters chain.executions (one inc a "
                                  "preview), chain.preview_dropped, the "
                                  "evm.* eight; its state and receipts "
                                  "are kept for chain.insert"),
    "chain.validate_candidate": ((), "an acceptor's whole check of a "
                                     "proposed block before its ACK "
                                     "(core/chain.py): body, senders, "
                                     "execution, the commitments; attrs "
                                     "number, txns, ok; counters "
                                     "chain.validated_blocks, "
                                     "chain.refused_candidates; a pass "
                                     "keeps its state and receipts for "
                                     "chain.insert"),
    "chain.verify_body": ((), "the transaction root of a block's body "
                              "(derive_sha over its encodings); attr txns"),
    "chain.execute": ((), "the apply_txn loop of process_block "
                          "(core/state.py); attrs txns, evm_calls (the "
                          "transactions that ran the interpreter) and "
                          "reverted (those of them that ended in "
                          "REVERT); counter chain.executions, one inc a "
                          "_process: a validation's, or the insert's of "
                          "a block that brings neither a validation nor "
                          "a preview of its own (a proposer's preview "
                          "counts there too); counters evm.calls, "
                          "evm.reverts, evm.ops, evm.sloads, "
                          "evm.sstores, evm.slot_deletes, evm.gas_used "
                          "and evm.gas_refunded, one inc a block "
                          "(core/evm.py Tally)"),
    "state.root": ((), "StateDB.root() where it is not cached: the dirty "
                       "accounts into the secure trie, then the nodes' "
                       "hashes; attr dirty; counter state.root_accounts, "
                       "one inc(dirty) a call"),
    "state.storage_root": ((), "inside state.root: the storage roots "
                               "of the dirty accounts whose storage was "
                               "written since its last root, before their "
                               "RLP is taken (on the library's rung the "
                               "nodes were hashed by the batch that wrote "
                               "them, a call at a time, and this only "
                               "reads the hash); attrs accounts, slots "
                               "(the writes since)"),
    "chain.receipts_root": ((), "derive_sha over a block's receipts "
                                "(core/state.py receipts_root); attr txns"),
    "chain.insert": ((), "execute, state root, index; attrs number, "
                         "txns, reused (1: the state and receipts are "
                         "chain.validate_candidate's or "
                         "chain.execute_preview's for this very body on "
                         "this head, nothing executed again); counters "
                         "chain.insert_reused, chain.insert_previewed"),
    "chain.recover_senders": ((), "a block's signed rows through the "
                                  "verifier in one call (core/state.py): "
                                  "behind the scheduler one window, part "
                                  "cache, part in flight, part device; "
                                  "attrs rows, native (rows whose "
                                  "signature and signing hash the one "
                                  "native pass over the body's wire bytes "
                                  "filled in), cached, coalesced, refused; "
                                  "counters chain.sender_rows, "
                                  "chain.sender_native_rows, "
                                  "chain.sender_cached_rows, "
                                  "chain.sender_coalesced_rows, "
                                  "chain.blocks_refused, one inc a call"),
    "rpc.handle": (("method",), "one HTTP request body dispatched (a batch "
                                "counts once, by its first method)"),
}

# ids: one draw of entropy a process, a counter under it.  A span id is
# the counter alone (it started at a random value, so two nodes' spans
# under one trace do not meet); a span that begins a trace gives it the
# process's random half over its own id: one draw of the counter a span.
# analysis: allow-determinism(trace/span ids are observability-only, never journaled)
_PROCESS_ID = os.urandom(8).hex()
# analysis: allow-determinism(trace/span ids are observability-only, never journaled)
_ids = itertools.count(int.from_bytes(os.urandom(8), "big"))

_thread_time = time.thread_time
_NO_LABELS = ((),)

# a span that is outermost on its thread reads the thread's CPU clock
# with probability 1 / CPU_EVERY (and every span inside it does), and
# observes its CPU time with that weight; 1 reads it on every span
CPU_EVERY = 8
# analysis: allow-determinism(which spans read the CPU clock is observability-only, never journaled)
_coin = random.Random(0x5eed).random

_trace_annotation = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` once this process has imported
    jax, else None: a node on the native verifier must not import jax
    for its spans.  With no profiler session the annotation is a flag
    test; that is "tracing off"."""
    global _trace_annotation
    if _trace_annotation is None:
        _trace_annotation = getattr(sys.modules.get("jax.profiler"),
                                    "TraceAnnotation", None)
    return _trace_annotation


@dataclass(frozen=True)
class SpanContext:
    """Immutable (trace_id, span_id) pair — what crosses process/node
    boundaries and what children parent themselves to."""

    trace_id: str  # 32 hex chars
    span_id: str   # 16 hex chars


class Span:
    """One timed operation.  Finished spans land in the tracer's ring
    buffer; unfinished ones are invisible to exporters.  As a context
    manager (what ``Tracer.span`` hands out) it is live for its body:
    annotated, timed on the wall clock and, one in :data:`CPU_EVERY`,
    on its thread's CPU clock, and the current context unless it stands
    alone (see :meth:`Tracer.span`).  ``cpu_s`` is the CPU time of the
    whole body, None for a span that did not read the clock (the other
    seven, ``record_span``)."""

    __slots__ = ("name", "parent_id", "start_s", "end_s", "cpu_s", "attrs",
                 "_tracer", "_id", "_trace", "_child_s", "_child_cpu",
                 "_cpu0", "_lone", "_live")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str | None,
                 parent_id: str | None, start_s: float, attrs: dict):
        self._tracer = tracer
        self.name = name
        # the ids are written out when somebody reads them: most spans
        # stand alone, and theirs are read by ``to_dict`` or never
        self._id = next(_ids)
        self._trace = trace_id
        self.parent_id = parent_id
        self.start_s = start_s
        self.end_s: float | None = None
        self.cpu_s: float | None = None
        self.attrs = attrs
        # what child spans on this thread covered, wall and CPU
        self._child_s = 0.0
        self._child_cpu = 0.0
        self._lone = False

    def __enter__(self) -> "Span":
        tracer = self._tracer
        stack = tracer._stack()
        if stack:
            timed = stack[-1]._cpu0 is not None  # as the span around it
        else:
            timed = _coin() * CPU_EVERY < 1.0
        if timed:
            # the slow read stays outside the wall interval, here and on
            # exit: the span's duration is what it is without the clock
            self._cpu0 = _thread_time()
            self.start_s = tracer._clock()
        else:
            self._cpu0 = None
        stack.append(self)
        ann = _annotation()
        if ann is not None:
            # whole-number attributes (a window's ``rows``, its lane's
            # ``device``) ride the annotation as the event's stats; its
            # name stays the span's
            ann = ann(self.name, **{k: v for k, v in self.attrs.items()
                                    if type(v) is int})
            ann.__enter__()
        self._live = (None if self._lone
                      else tracer._current.set(self.context()),
                      profiler.tag_span(self.name), ann, stack)
        return self

    def __exit__(self, *exc) -> None:
        self.end()
        cpu = None
        if self._cpu0 is not None:
            cpu = self.cpu_s = _thread_time() - self._cpu0
        token, ptok, ann, stack = self._live
        self._live = None
        if ann is not None:
            ann.__exit__(*exc)
        if ptok is not None:
            profiler.pop_phase(ptok)
        tracer = self._tracer
        if token is not None:
            tracer._current.reset(token)
        stack.pop()  # self: ``with`` blocks of one thread end innermost first
        dur = self.end_s - self.start_s
        if stack:
            outer = stack[-1]
            outer._child_s += dur
            if cpu is not None:
                outer._child_cpu += cpu
        tracer._observe(self, dur, max(0.0, dur - self._child_s),
                        None if cpu is None
                        else max(0.0, cpu - self._child_cpu))

    @property
    def span_id(self) -> str:
        return "%016x" % (self._id & 0xFFFFFFFFFFFFFFFF)

    @property
    def trace_id(self) -> str:
        # no trace to join: the span begins one, under its own id
        return self._trace or _PROCESS_ID + self.span_id

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def end(self) -> None:
        if self.end_s is not None:
            return
        self.end_s = self._tracer._clock()
        self._tracer._finish(self)

    @property
    def duration_s(self) -> float:
        if self.end_s is None:
            return 0.0
        return self.end_s - self.start_s

    def to_dict(self) -> dict:
        out = {"name": self.name, "trace": self.trace_id,
               "span": self.span_id, "parent": self.parent_id,
               "start_s": round(self.start_s, 6),
               "duration_s": round(self.duration_s, 6),
               "attrs": dict(self.attrs)}
        if self.cpu_s is not None:
            out["cpu_s"] = round(self.cpu_s, 6)
        return out


class Tracer:
    """Span factory + bounded in-memory exporter.

    Finished spans go into a deque ring buffer (oldest dropped first, a
    dropped counter keeps the loss observable).  The "current" span is a
    contextvar, so nesting works across ``await`` points but — by design
    — not across ``SimClock.call_later`` hops; callers that cross a
    scheduler boundary carry a ``SpanContext`` explicitly (see
    ``core/txpool.py``).
    """

    def __init__(self, clock=time.monotonic, capacity: int = 4096,
                 metrics: "metrics_mod.Registry | None" = None):
        self._clock = clock
        # the registry the spans' histograms live in
        self.metrics = metrics if metrics is not None \
            else metrics_mod.DEFAULT
        self._lock = threading.Lock()
        # live spans of each thread, innermost last (self time)
        self._tls = threading.local()
        # span name, or (name, label values...) -> ((wall, self), cpu)
        self._hists: dict = {}
        self._finished: deque[Span] = deque(maxlen=capacity)
        self._current: ContextVar[SpanContext | None] = ContextVar(
            "geec_trace_ctx", default=None)
        # spans that entered the ring (``stats()["started"]``: a span is
        # counted where it ends, under the ring's one lock hold)
        self.started = 0
        self.dropped = 0

    # -- span lifecycle -------------------------------------------------
    def _open(self, name: str, parent, attrs: dict) -> Span:
        if parent is None:
            return Span(self, name, None, None, self._clock(), attrs)
        return Span(self, name, parent.trace_id, parent.span_id,
                    self._clock(), attrs)

    def span(self, name: str, parent=_UNSET, root: bool = False,
             **attrs) -> Span:
        """A span to use as ``with tracer.span(...) as sp``: ended on
        exit, annotated in the profiler's trace and observed into
        ``span.seconds`` / ``span.self_seconds`` /
        ``span.self_cpu_seconds``.

        It joins the trace it runs under (or the ``parent`` given) and
        is the current context for its body.  Where no trace is current,
        ``root=True`` begins one (a transaction's, at ``txpool.ingest``;
        an explicit ``parent=None`` forces one); otherwise the span
        stands alone: timed, recorded and annotated, but not made
        current.  A window or a message handled for nobody's transaction
        must not ride outbound messages as a 28-byte header nor tag the
        journal's events with an id of its own: every consensus round
        would become one endless trace, and the journal would stop being
        byte-deterministic under the simulator.

        Span names in ``profiler.SPAN_PHASES`` also tag the calling
        thread with the matching pipeline phase for the span body — the
        bridge that lets the continuous sampling profiler attribute
        CPU samples to ``pool_admit`` etc. without its own hooks on
        every ingest path (one dict probe per span when unmapped)."""
        lone = False
        if parent is _UNSET:
            parent = self._current.get()
            lone = parent is None and not root
        sp = self._open(name, parent, attrs)
        sp._lone = lone
        return sp

    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def _observe(self, span: Span, dur: float, self_s: float,
                 self_cpu: float | None) -> None:
        """The live span's two wall-time observations, in one hold of
        one lock and one reservoir draw (``metrics.observe_together``),
        and its self CPU time where it read the clock, as CPU_EVERY
        observations: it stands for that many spans."""
        name = span.name
        labels = SPANS.get(name, _NO_LABELS)[0]
        key = (name, *[span.attrs.get(k) for k in labels]) if labels \
            else name
        hists = self._hists.get(key)
        if hists is None:
            tail = name + "".join(
                f",{k}={v}" for k, v in zip(labels, key[1:])
                if v is not None)
            hists = self._hists[key] = (
                metrics_mod.together(
                    self.metrics.histogram(f"span.seconds;name={tail}"),
                    self.metrics.histogram(
                        f"span.self_seconds;name={tail}")),
                self.metrics.histogram(
                    f"span.self_cpu_seconds;name={tail}"))
        metrics_mod.observe_together(hists[0], (dur, self_s))
        if self_cpu is not None:
            hists[1].observe(self_cpu, CPU_EVERY)

    def record_span(self, name: str, duration_s: float, parent=_UNSET,
                    **attrs) -> Span:
        """Record an already-measured duration as a finished span (used
        by virtual-clock phases where wall time is meaningless; it has
        no CPU time and observes no histogram)."""
        if parent is _UNSET:
            parent = self._current.get()
        sp = self._open(name, parent, attrs)
        sp.start_s -= duration_s
        sp.end_s = sp.start_s + duration_s
        self._finish(sp)
        return sp

    def _finish(self, span: Span) -> None:
        # the ring keeps the span itself: ``finished`` / ``dump`` make
        # the dict, when somebody asks
        with self._lock:
            self.started += 1
            if len(self._finished) == self._finished.maxlen:
                self.dropped += 1
            self._finished.append(span)

    # -- context plumbing -----------------------------------------------
    def current_context(self) -> SpanContext | None:
        return self._current.get()

    @contextmanager
    def activate(self, ctx: SpanContext | None):
        """Make ``ctx`` the current context for the body (no-op if
        None — receivers call this unconditionally on every message)."""
        if ctx is None:
            yield
            return
        token = self._current.set(ctx)
        try:
            yield
        finally:
            self._current.reset(token)

    # -- export ---------------------------------------------------------
    def finished(self, limit: int = 0, trace: str | None = None) -> list[dict]:
        """Most-recent-last finished spans, optionally filtered by trace
        id and capped to the newest ``limit``."""
        with self._lock:
            spans = list(self._finished)
        if trace:
            spans = [s for s in spans if s.trace_id == trace]
        if limit and limit > 0:
            spans = spans[-limit:]
        return [s.to_dict() for s in spans]

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()

    def dump(self, path: str, drain: bool = True) -> int:
        """Append finished spans to ``path`` as JSONL; returns the number
        written.  ``drain`` empties the buffer so periodic dumps don't
        duplicate rows."""
        with self._lock:
            spans = list(self._finished)
            if drain:
                self._finished.clear()
        if not spans:
            return 0
        with open(path, "a", encoding="utf-8") as fh:
            for s in spans:
                fh.write(json.dumps(s.to_dict(), sort_keys=True) + "\n")
        return len(spans)

    def stats(self) -> dict:
        with self._lock:
            return {"started": self.started, "buffered": len(self._finished),
                    "dropped": self.dropped,
                    "capacity": self._finished.maxlen}


# -- wire-format helpers -----------------------------------------------

def inject(ctx: SpanContext | None, data: bytes) -> bytes:
    """Prepend the trace header for ``ctx`` (pass-through when None)."""
    if ctx is None:
        return data
    return (MAGIC + bytes.fromhex(ctx.trace_id)
            + bytes.fromhex(ctx.span_id) + data)


def inject_current(data: bytes, tracer: "Tracer | None" = None) -> bytes:
    """Prepend the *active* trace context, if any."""
    return inject((tracer or DEFAULT).current_context(), data)


def extract(data: bytes) -> tuple[SpanContext | None, bytes]:
    """Split an incoming payload into (context-or-None, real payload)."""
    if data[:4] == MAGIC and len(data) >= _HEADER_LEN:
        ctx = SpanContext(data[4:20].hex(), data[20:28].hex())
        return ctx, data[_HEADER_LEN:]
    return None, data


def payload_of(data: bytes) -> bytes:
    """The RLP payload regardless of a trace header — for protocol muxes
    that peek at message codes before dispatch."""
    if data[:4] == MAGIC and len(data) >= _HEADER_LEN:
        return data[_HEADER_LEN:]
    return data


DEFAULT = Tracer()
