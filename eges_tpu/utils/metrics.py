"""Metrics registry: counters / gauges / meters / timers / histograms.

Role parity with the reference's ``metrics/`` fork (ref:
metrics/metrics.go:25 ``--metrics`` flag; instrumented in p2p/metrics.go,
eth/metrics.go, eth/downloader/metrics.go).  In-process registry with
snapshot export; the RPC layer and harness read snapshots instead of the
reference's influxdb/librato push exporters, and ``prometheus_text``
renders the whole registry in Prometheus text exposition format 0.0.4
for the RPC server's ``GET /metrics``.

Label convention: the registry is flat, so labeled series are encoded in
the metric name as ``family;key=value,key2=value2`` (e.g.
``verifier.device_seconds;bucket=128``).  The Prometheus exporter parses
that back into real labels; ``snapshot()`` keeps the flat names.
"""

from __future__ import annotations

import math
import random
import re
import threading
import time
from collections import deque

# Closed vocabulary of metric families emitted by the library (the part
# of the name before the ``;`` label separator).  Emit sites are checked
# against this set by ``python -m harness.analysis`` (vocabulary rule):
# an unregistered family, a family used as two different kinds, or a
# registered family with no emit site all fail the gate.
METRIC_FAMILIES = frozenset({
    # core/chain.py
    "chain.bad_blocks", "chain.blocks", "chain.fastsync_adoptions",
    "chain.geec_txns", "chain.height", "chain.insert",
    "chain.insert_seconds", "chain.txns",
    # core/state.py recover_senders — a block's signed rows handed to
    # the verifier, those the one native pass over the body filled in,
    # those the scheduler's cache answered, those that joined a pending
    # row, and the blocks it refused
    "chain.blocks_refused", "chain.sender_cached_rows",
    "chain.sender_coalesced_rows", "chain.sender_native_rows",
    "chain.sender_rows",
    # the block path (PR 44): an acceptor's validations by outcome, the
    # executions of a block's transactions (one a _process), the inserts
    # that took the state and receipts of the block's own validation
    # (PR 45) or of the preview it was built from (PR 49), the accounts
    # StateDB.root() hashed into the trie
    "chain.executions", "chain.insert_previewed", "chain.insert_reused",
    "chain.refused_candidates", "chain.validated_blocks",
    "state.root_accounts",
    # the proposer's half (PR 48): the transactions a preview dropped
    # because they could not execute
    "chain.preview_dropped",
    # core/trie.py — the nodes a root encoded and hashed (derive_sha and
    # the persistent trie, one inc a root), those of them the library
    # did (native/trie.cpp), the keys its node store took in batches,
    # and the store's live nodes (read off the library when the
    # registry is read)
    "trie.native_nodes", "trie.native_updates", "trie.nodes",
    "trie.store_nodes",
    # core/evm.py Tally (PR 51) — what the transactions that ran the
    # interpreter did, summed a block and added once by chain.execute
    # and chain.execute_preview
    "evm.calls", "evm.gas_refunded", "evm.gas_used", "evm.ops",
    "evm.reverts", "evm.sloads", "evm.slot_deletes", "evm.sstores",
    # consensus/
    "consensus.deferred_depth", "consensus.deferred_dropped",
    "consensus.elected", "consensus.forced_empties",
    "consensus.geec_txn_dropped", "consensus.ingress_oversized",
    "consensus.phase_seconds", "consensus.reg_req_dropped",
    "consensus.sealed", "membership.min_ttl", "membership.size",
    # the proposer's half (PR 48): proposals built, and the bytes of the
    # validate requests gossiped (retries too)
    "consensus.proposals_built", "consensus.request_bytes",
    # consensus/quorum.py — a quorum's tally: attempts (every collected
    # signature through the verifier), the signatures they handed over,
    # the authors they pruned, the quorums certified, and the time from
    # the count first standing at the threshold to the quorum certified
    "consensus.quorum_attempts", "consensus.quorum_pruned",
    "consensus.quorum_rows", "consensus.quorum_seconds",
    "consensus.quorums",
    # ingress/columnar.py — frames handed to decode_window, and those
    # that went through the native window decoder
    "ingress.decode_native_rows", "ingress.decode_rows",
    # net/ + sim/simnet.py
    "net.dead_letters", "net.direct_bytes", "net.direct_msgs",
    "net.gossip_bytes", "net.gossip_msgs", "net.peer_count",
    # node/service.py — how late the event loop's 20 ms tick fires
    "service.loop_lag_seconds",
    # utils/tracing.py — every live span's duration, self time and
    # self CPU time, labelled ``;name=<span>[,<label>=<value>]``
    "span.seconds", "span.self_cpu_seconds", "span.self_seconds",
    # utils/profiler.py read_cpu — the process's CPU time and its live
    # Python threads' by role (``;role=<role>``), set when the DEFAULT
    # registry is read
    "process.cpu_seconds", "threads.cpu_seconds",
    # utils/heap.py settle — what the process's ONE collect-and-freeze
    # moved into the collector's permanent generation as its verify
    # path first served, and what that took
    "process.gc_frozen_objects", "process.gc_settle_seconds",
    # sim/faults.py — deterministic fault injection
    "sim.faults_injected",
    # core/txpool.py
    "txpool.commit_records", "txpool.commit_rows", "txpool.known_clears",
    "txpool.pending", "txpool.window_undecoded",
    # crypto/ verifiers
    "verifier.batches", "verifier.compile_cache_hits",
    "verifier.compile_cache_misses", "verifier.d2h_seconds",
    "verifier.device", "verifier.device_name", "verifier.device_seconds",
    "verifier.h2d_seconds", "verifier.host_rows", "verifier.native",
    "verifier.native_batches", "verifier.native_rows",
    "verifier.pad_waste", "verifier.padded_rows", "verifier.rows",
    # crypto/scheduler.py — coalescing scheduler + sender-recovery cache
    "verifier.cache_hits", "verifier.cache_misses",
    "verifier.prewarmed_buckets", "verifier.sched_batch_rows",
    "verifier.sched_occupancy", "verifier.sched_queue_wait_seconds",
    "verifier.singleton_batches",
    # crypto/scheduler.py — fail-safe circuit breaker around the device
    "verifier.breaker_probes", "verifier.breaker_state",
    "verifier.breaker_trips", "verifier.device_errors",
    # crypto/scheduler.py — mesh dispatch (per-device window lanes);
    # the per-device families carry a ``;device=N`` label
    "verifier.mesh_devices", "verifier.mesh_occupancy",
    "verifier.mesh_queue_depth", "verifier.mesh_rows",
    "verifier.mesh_straggler_diverts", "verifier.mesh_window_splits",
    # crypto/aotstore.py + crypto/verifier.py — AOT-serialized
    # executables: artifact save/load/export accounting, persistent
    # compile-cache hardening, and service cold-start time
    "verifier.aot_compiles", "verifier.aot_export_seconds",
    "verifier.aot_load_errors", "verifier.aot_load_seconds",
    "verifier.aot_loads", "verifier.aot_saves",
    "verifier.cold_start_seconds", "verifier.compile_cache_errors",
    # crypto/scheduler.py — double-buffered window pipeline: fraction
    # of lane windows whose H2D staging overlapped the previous
    # window's compute/D2H
    "verifier.pipeline_overlap_ratio",
    # crypto/scheduler.py — window flight recorder (bounded lifecycle
    # ring behind the thw_flight RPC)
    "verifier.flight_windows",
    # crypto/scheduler.py — flight-ring overflow (oldest window evicted
    # before anything read it; the ring's silent-loss signal)
    "verifier.flight_dropped",
    # crypto/scheduler.py — hedged re-dispatch of straggling windows:
    # speculative duplicates placed, duplicates that won, losers
    # cancelled before execution, losers that ran to waste
    "verifier.hedge_cancelled", "verifier.hedge_wasted",
    "verifier.hedge_wins", "verifier.hedges",
    # crypto/sidecar.py — the verify sidecar's socket, in the sidecar's
    # process (what it served) and in a client's (what it asked): the
    # windows and their rows, the bytes of their frames, rows a client
    # answered on its own host because the sidecar could not, frames
    # that were none, reads held back while a connection's windows in
    # flight stood at their bound, connected clients, and a window's
    # time inside the sidecar from its entry to its last answer
    "sidecar.backpressure_waits", "sidecar.bytes_in",
    "sidecar.bytes_out", "sidecar.clients", "sidecar.fallback_rows",
    "sidecar.rows", "sidecar.served_seconds", "sidecar.torn_frames",
    "sidecar.windows",
    # consensus/node.py — snapshot state sync: durable checkpoints,
    # O(tail) restarts, byzantine-tolerant live sync, and the billed,
    # bounded snapshot-serving plane
    "statesync.aborts", "statesync.checkpoint_bytes",
    "statesync.checkpoints", "statesync.oversized_reply",
    "statesync.pages_accepted", "statesync.pages_rejected",
    "statesync.pages_served", "statesync.poisoned",
    "statesync.reanchors", "statesync.restart_replayed",
    "statesync.resumes", "statesync.serve_throttled",
    # utils/timeseries.py + harness/collector.py — telemetry plane
    "telemetry.envelopes", "telemetry.samples",
    # harness/slo.py — burn-rate SLO engine
    "slo.alerts_firing", "slo.transitions",
    # harness/anatomy.py — commit critical-path assembler
    "anatomy.blocks",
    # eges_tpu/utils/ledger.py — ingress provenance ledger
    "ledger.evictions", "ledger.origins", "ledger.rejects",
    "ledger.rows", "ledger.snapshots",
    # eges_tpu/utils/profiler.py — continuous sampling profiler
    "profiler.dropped", "profiler.hz", "profiler.overhead_pct",
    "profiler.reports", "profiler.samples",
    # eges_tpu/utils/devstats.py — device-efficiency observatory; the
    # goodput and HBM-watermark families carry a ``;device=N`` label
    "devstats.goodput_ratio", "devstats.mem_bytes_in_use",
    "devstats.mem_limit_bytes", "devstats.mem_peak_bytes",
    "devstats.reports", "devstats.trace_captures",
})

# One-line help string per registered family, emitted as ``# HELP``
# lines by ``prometheus_text`` and kept exhaustive by the vocabulary
# checker (``python -m harness.analysis``): a family registered above
# without a help entry here fails the gate.
METRIC_HELP = {
    "chain.bad_blocks": "Blocks rejected by validation on insert.",
    "chain.blocks": "Canonical blocks inserted into the chain.",
    "chain.executions": (
        "Executions of a block's transactions (one a _process: an "
        "acceptor's validation, or the insert of a block this node "
        "neither validated nor previewed on this head; one a proposer's "
        "execute_preview, whose outcome is kept for that block's insert)."),
    "chain.insert_previewed": (
        "Inserts that took the state and receipts of the execute_preview "
        "the block was built from instead of executing it again."),
    "chain.insert_reused": (
        "Inserts that took the state and receipts of the block's own "
        "validate_candidate instead of executing it again."),
    "chain.preview_dropped": (
        "Transactions a proposer's execute_preview left out of its "
        "block because they could not execute."),
    "chain.refused_candidates": (
        "Proposed blocks validate_candidate refused (no ACK)."),
    "chain.validated_blocks": (
        "Proposed blocks validate_candidate took (an ACK follows)."),
    "state.root_accounts": (
        "Dirty accounts StateDB.root() put into the secure trie."),
    "trie.nodes": (
        "Trie nodes encoded and hashed for a root (derive_sha and "
        "IncrementalTrie.root; a node that has its reference is never "
        "counted again)."),
    "trie.native_nodes": (
        "Trie nodes the native library encoded and hashed, one call a "
        "root (trie.nodes less these took the Python rung)."),
    "trie.native_updates": (
        "Keys the library's node store took, one batch a call (over "
        "state.root_accounts plus storage writes: how often the store "
        "engages)."),
    "trie.store_nodes": (
        "Live nodes of the library's trie node store (falls when a "
        "fork or a pruned height's state is dropped)."),
    "evm.calls": (
        "Transactions of executed blocks that ran the interpreter "
        "(creations, calls into code, precompiles): one a transaction, "
        "added once a block."),
    "evm.reverts": (
        "Of evm.calls, those whose root frame ended in REVERT (status "
        "0, their gas charged, no write kept)."),
    "evm.ops": (
        "Opcodes the interpreter ran, every frame's, reverted or not."),
    "evm.sloads": "SLOADs among evm.ops.",
    "evm.sstores": "SSTOREs among evm.ops.",
    "evm.slot_deletes": (
        "Slots written 0 over a value by frames that were kept: each "
        "leaves the storage trie and earns the 15,000 refund."),
    "evm.gas_used": (
        "Gas charged to the transactions counted in evm.calls, after "
        "the refund."),
    "evm.gas_refunded": (
        "Gas given back from the refund counter, capped at half of "
        "what a transaction used."),
    "chain.blocks_refused": (
        "Blocks whose sender recovery raised StateError (a signature "
        "that names no sender)."),
    "chain.sender_cached_rows": (
        "Rows of block sender recovery that the scheduler's recovery "
        "cache answered."),
    "chain.sender_coalesced_rows": (
        "Rows of block sender recovery that joined a row already "
        "pending in the scheduler."),
    "chain.sender_native_rows": (
        "Rows of block sender recovery whose signature and signing "
        "hash the native pass over the body's wire bytes filled in."),
    "chain.sender_rows": (
        "Signed rows of blocks handed to the verifier by "
        "recover_senders."),
    "chain.fastsync_adoptions": "Fast-sync snapshot adoptions.",
    "chain.geec_txns": "Geec control-plane transactions inserted.",
    "chain.height": "Current canonical chain height.",
    "chain.insert": "Block insert attempts.",
    "chain.insert_seconds": "Block insert latency in seconds.",
    "chain.txns": "Payload transactions inserted with blocks.",
    "consensus.deferred_depth": "Events parked on the deferred queue.",
    "consensus.deferred_dropped": "Oldest deferrals evicted at DEFER_MAX.",
    "consensus.elected": "Elections won by this node.",
    "consensus.forced_empties": "Empty blocks forced by round timeout.",
    "consensus.geec_txn_dropped": "UDP geec txns shed by size or backlog cap.",
    "consensus.ingress_oversized": "Datagrams dropped by the ingress "
                                   "byte cap before decode.",
    "consensus.reg_req_dropped": "Pending registrations evicted at "
                                 "REG_PENDING_MAX.",
    "consensus.phase_seconds": "Consensus phase duration in seconds.",
    "consensus.quorum_attempts": "Attempts at a quorum: every collected "
                                 "signature through the verifier.",
    "consensus.quorum_pruned": "Authors an attempt dropped for want of a "
                               "valid signature.",
    "consensus.quorum_rows": "Signatures the attempts handed to the "
                             "verifier.",
    "consensus.quorum_seconds": "From the count of replies first standing "
                                "at the threshold to the quorum certified.",
    "consensus.quorums": "Quorums certified (election, ACK, query).",
    "consensus.proposals_built": "Proposals this node built as the "
                                 "elected proposer.",
    "consensus.request_bytes": "Bytes of the validate requests gossiped "
                               "(the whole block in each; retries too).",
    "consensus.sealed": "Blocks sealed by this node.",
    "membership.min_ttl": "Minimum TTL across registered members.",
    "membership.size": "Registered committee members.",
    "ingress.decode_native_rows": (
        "Frames of columnar ingest windows that went through the "
        "native window decoder."),
    "ingress.decode_rows": (
        "Frames handed to the columnar window decoder."),
    "net.dead_letters": "Messages dropped with no deliverable peer.",
    "net.direct_bytes": "Bytes sent over the direct (point-to-point) plane.",
    "net.direct_msgs": "Messages sent over the direct plane.",
    "net.gossip_bytes": "Bytes sent over the gossip plane.",
    "net.gossip_msgs": "Messages sent over the gossip plane.",
    "net.peer_count": "Currently connected peers.",
    "service.loop_lag_seconds": (
        "Lateness of the service event loop's 20 ms tick, in seconds."),
    "sim.faults_injected": "Scripted faults injected by the chaos harness.",
    "process.cpu_seconds": (
        "CPU time of the whole process, every native thread included, "
        "read when the registry is read, in seconds."),
    "process.gc_frozen_objects": (
        "Objects in the collector's permanent generation since the "
        "process settled its heap: what no later collection walks."),
    "process.gc_settle_seconds": (
        "What the one collection and freeze took as the process's "
        "verify path first served, in seconds."),
    "threads.cpu_seconds": (
        "CPU time of the live Python threads of one role, read when the "
        "registry is read, in seconds."),
    "span.seconds": "Duration of a program span, by name, in seconds.",
    "span.self_cpu_seconds": (
        "CPU time a span's thread ran inside it, less its child spans' "
        "on the same thread, in seconds."),
    "span.self_seconds": (
        "A span's duration minus what its child spans on the same "
        "thread covered, in seconds."),
    "txpool.commit_records": (
        "tx.commit records written by remove_included: one an ingest "
        "trace among a block's transactions."),
    "txpool.commit_rows": (
        "Transactions handed to remove_included whose ingest context "
        "was known."),
    "txpool.known_clears": "Coarse clears of the known-txn dedup set.",
    "txpool.pending": "Transactions pending in the pool.",
    "txpool.window_undecoded": (
        "Rows of a columnar ingest window dropped because the frame "
        "failed to decode."),
    "verifier.batches": "Signature verification batches dispatched.",
    "verifier.compile_cache_hits": "Verifier JIT compile-cache hits.",
    "verifier.compile_cache_misses": "Verifier JIT compile-cache misses.",
    "verifier.d2h_seconds": "Device-to-host transfer seconds.",
    "verifier.device": "Accelerator devices visible to the verifier.",
    "verifier.device_name": "Accelerator device platform/name label.",
    "verifier.device_seconds": "On-device compute seconds per batch.",
    "verifier.h2d_seconds": "Host-to-device transfer seconds.",
    "verifier.host_rows": "Rows verified on the host fallback path.",
    "verifier.native": "Whether the native host verifier is loaded.",
    "verifier.native_batches": "Batches served by the native host verifier.",
    "verifier.native_rows": "Rows served by the native host verifier.",
    "verifier.pad_waste": "Rows of padding added to reach bucket sizes.",
    "verifier.padded_rows": "Total rows after bucket padding.",
    "verifier.rows": "Signature rows submitted for verification.",
    "verifier.cache_hits": "Sender-recovery cache hits.",
    "verifier.cache_misses": "Sender-recovery cache misses.",
    "verifier.prewarmed_buckets": "Buckets compiled ahead of traffic.",
    "verifier.sched_batch_rows": "Rows per coalesced scheduler window.",
    "verifier.sched_occupancy": "Dispatched rows over padded bucket rows.",
    "verifier.sched_queue_wait_seconds":
        "Seconds a submission waited in the coalescing window.",
    "verifier.singleton_batches": "Single-row windows diverted to the host.",
    "verifier.breaker_probes": "Half-open circuit-breaker probe dispatches.",
    "verifier.breaker_state": "Circuit breaker state (0 closed, 1 open).",
    "verifier.breaker_trips": "Circuit breaker open transitions.",
    "verifier.device_errors": "Device dispatch failures.",
    "verifier.mesh_devices": "Device lanes in the mesh dispatcher.",
    "verifier.mesh_occupancy": "Per-device window occupancy.",
    "verifier.mesh_queue_depth": "Windows queued per device lane.",
    "verifier.mesh_rows": "Rows served per device lane.",
    "verifier.mesh_straggler_diverts":
        "Lane windows rescued to the host by the straggler policy.",
    "verifier.mesh_window_splits": "Windows split across device lanes.",
    "verifier.aot_compiles": "AOT executables compiled (cache miss).",
    "verifier.aot_export_seconds": "AOT artifact export seconds.",
    "verifier.aot_load_errors": "AOT artifact load failures.",
    "verifier.aot_load_seconds": "AOT artifact deserialize seconds.",
    "verifier.aot_loads": "AOT executables loaded from the artifact store.",
    "verifier.aot_saves": "AOT executables serialized to the artifact store.",
    "sidecar.backpressure_waits": (
        "Reads a sidecar connection held back while its windows in "
        "flight stood at their bound."),
    "sidecar.bytes_in": "Bytes of sidecar frames received.",
    "sidecar.bytes_out": "Bytes of sidecar frames sent.",
    "sidecar.clients": "Node processes connected to the verify sidecar.",
    "sidecar.fallback_rows": (
        "Rows a sidecar client recovered on its own host because the "
        "sidecar gave no answer."),
    "sidecar.rows": "Signature rows carried by sidecar windows.",
    "sidecar.served_seconds": (
        "A window's time inside the sidecar, from its entry into the "
        "scheduler to its last answer."),
    "sidecar.torn_frames": (
        "Connections the sidecar ended for a frame that was none."),
    "sidecar.windows": "Windows answered through the verify sidecar.",
    "verifier.cold_start_seconds":
        "Service cold start: verifier ready after process start.",
    "verifier.compile_cache_errors": "Persistent compile-cache failures.",
    "verifier.pipeline_overlap_ratio":
        "Lane windows whose staging overlapped the previous compute.",
    "verifier.flight_windows":
        "Windows recorded by the lifecycle flight recorder.",
    "verifier.flight_dropped":
        "Flight-recorder windows evicted unread by ring overflow.",
    "verifier.hedge_cancelled":
        "Hedged duplicates cancelled before execution (winner first).",
    "verifier.hedge_wasted":
        "Hedged duplicates that ran after the winner (wasted work).",
    "verifier.hedge_wins": "Straggling windows won by the hedge copy.",
    "verifier.hedges": "Speculative duplicate dispatches placed.",
    "statesync.aborts": "Fast syncs aborted back to full block replay.",
    "statesync.checkpoint_bytes": "Size of the newest durable checkpoint.",
    "statesync.checkpoints": "Durable state checkpoints written.",
    "statesync.oversized_reply": "State replies dropped by the pre-decode "
                                 "byte cap.",
    "statesync.pages_accepted": "State pages staged from serving peers.",
    "statesync.pages_rejected": "State pages rejected (unsolicited, "
                                "out-of-order, or unattributable).",
    "statesync.pages_served": "State pages served to fetching peers.",
    "statesync.poisoned": "Downloads rejected by the pivot root check.",
    "statesync.reanchors": "Downloads re-anchored on a fresh pivot/server.",
    "statesync.restart_replayed": "Tail blocks replayed on the last restart.",
    "statesync.resumes": "Syncs resumed from crash-staged pages.",
    "statesync.serve_throttled": "State fetches dropped by the per-peer "
                                 "serve rate limit.",
    "telemetry.envelopes": "Telemetry envelopes ingested by the collector.",
    "telemetry.samples": "Registry samples taken by the telemetry sampler.",
    "slo.alerts_firing": "SLO objectives currently in the firing state.",
    "slo.transitions": "SLO alert state-machine transitions journaled.",
    "anatomy.blocks": "Committed blocks assembled by the anatomy profiler.",
    "ledger.evictions": "Origins evicted by space-saving top-K tracking.",
    "ledger.origins": "Origins currently tracked by the ingress ledger.",
    "ledger.rejects": "Ingress rejects booked to origins by the ledger.",
    "ledger.rows": "Verifier rows booked to origins by the ledger.",
    "ledger.snapshots": "Per-block ingress_ledger snapshots journaled.",
    "profiler.dropped": "Profiler samples lost to walk races or stack caps.",
    "profiler.hz": "Configured stack-sampling rate of the CPU profiler.",
    "profiler.overhead_pct": "Profiler self-cost as % of elapsed wall time.",
    "profiler.reports": "profiler_report events folded by the collector.",
    "profiler.samples": "Thread stack samples captured by the CPU profiler.",
    "devstats.goodput_ratio":
        "Useful rows over padded device rows per lane (last tick).",
    "devstats.mem_bytes_in_use": "Device HBM bytes currently in use.",
    "devstats.mem_limit_bytes": "Device HBM allocation limit in bytes.",
    "devstats.mem_peak_bytes": "Device HBM peak bytes-in-use watermark.",
    "devstats.reports": "device_efficiency events folded by the collector.",
    "devstats.trace_captures": "On-demand device trace captures completed.",
}


def percentile(sorted_vals, q: float) -> float:
    """Linear-interpolation percentile over a pre-sorted sequence,
    matching numpy.percentile's default method."""
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    if n == 1:
        return float(sorted_vals[0])
    rank = (q / 100.0) * (n - 1)
    lo = int(rank)
    frac = rank - lo
    if lo + 1 >= n:
        return float(sorted_vals[-1])
    return float(sorted_vals[lo]) + frac * (
        float(sorted_vals[lo + 1]) - float(sorted_vals[lo]))


class Counter:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = v


class Meter:
    """Event rate: count + rate over the process lifetime and a 1-minute
    sliding window."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.count = 0
        self._start = clock()
        self._window: deque[tuple[float, int]] = deque()

    def mark(self, n: int = 1) -> None:
        with self._lock:
            self.count += n
            now = self._clock()
            self._window.append((now, n))
            cutoff = now - 60.0
            while self._window and self._window[0][0] < cutoff:
                self._window.popleft()

    @property
    def rate_mean(self) -> float:
        dt = self._clock() - self._start
        return self.count / dt if dt > 0 else 0.0

    @property
    def rate_1m(self) -> float:
        with self._lock:
            return sum(n for _, n in self._window) / 60.0


class Timer:
    """Duration accumulator with count/total/min/max/mean."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def update(self, seconds: float) -> None:
        with self._lock:
            self.count += 1
            self.total += seconds
            self.min = min(self.min, seconds)
            self.max = max(self.max, seconds)

    def time(self):
        t0 = self._clock()
        timer = self

        class _Ctx:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                timer.update(timer._clock() - t0)

        return _Ctx()

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0


class Histogram:
    """Reservoir-sampled distribution (Vitter's Algorithm R, fixed-size
    uniform reservoir) with exact count/total/min/max and interpolated
    percentiles over the sample.

    A seeded PRNG keeps test runs deterministic; below ``reservoir``
    observations the percentiles are exact.
    """

    RESERVOIR = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._rng = random.Random(0x5eed)
        self._sample: list[float] = []
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def observe(self, value: float, n: int = 1) -> None:
        """``n`` observations of one value (a window's rows that waited
        the same time) in one lock hold: ``count``, ``total``, ``min``,
        ``max`` and the mean are what ``n`` calls give.  The reservoir
        takes those there is room for; the rest overwrite as many
        random slots as Algorithm R accepts of them in expectation (the
        sum of ``RESERVOIR / count`` over their counts)."""
        if n < 1:
            return
        v = float(value)
        with self._lock:
            self.count += n
            self.total += v * n
            self.min = min(self.min, v)
            self.max = max(self.max, v)
            room = min(n, self.RESERVOIR - len(self._sample))
            if room > 0:
                self._sample.extend([v] * room)
                n -= room
            if n == 1:
                j = self._rng.randrange(self.count)
                if j < self.RESERVOIR:
                    self._sample[j] = v
            elif n > 1:
                keep = self.RESERVOIR * math.log(
                    self.count / (self.count - n))
                for _ in range(int(keep)
                               + (self._rng.random() < keep % 1.0)):
                    self._sample[self._rng.randrange(self.RESERVOIR)] = v

    def _take(self, v: float, slot: int | None) -> int | None:
        """One observation with the lock held by the caller
        (:func:`observe_together`); ``slot`` is Algorithm R's draw where
        a histogram observed in step has made it already.  Returns the
        draw."""
        self.count += 1
        self.total += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if len(self._sample) < self.RESERVOIR:
            self._sample.append(v)
            return slot
        if slot is None:
            slot = int(self._rng.random() * self.count)
        if slot < self.RESERVOIR:
            self._sample[slot] = v
        return slot

    def percentile(self, q: float) -> float:
        with self._lock:
            vals = sorted(self._sample)
        return percentile(vals, q)

    def percentiles(self, qs=(50.0, 95.0, 99.0)) -> dict[float, float]:
        with self._lock:
            vals = sorted(self._sample)
        return {q: percentile(vals, q) for q in qs}

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0


def together(*hists: Histogram) -> tuple:
    """Make histograms that are only ever observed together (a span's
    duration and self time) share the first's lock, so
    that :func:`observe_together` covers all in one hold and a reader of
    any of them excludes the writer."""
    for h in hists[1:]:
        h._lock = hists[0]._lock
    return hists


def observe_together(hists: tuple, values: tuple) -> None:
    """One observation each of histograms joined by :func:`together`:
    one lock hold and, once the reservoirs are full, ONE draw for all
    (their counts move in step, so Algorithm R's slot is the same, and
    the reservoirs keep the same observations)."""
    with hists[0]._lock:
        slot = None
        for h, v in zip(hists, values):
            slot = h._take(v, slot)


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = {}

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls()
                self._metrics[name] = m
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def meter(self, name: str) -> Meter:
        return self._get(name, Meter)

    def timer(self, name: str) -> Timer:
        return self._get(name, Timer)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def _read(self) -> list:
        """The metrics by name, for ``snapshot`` and
        ``prometheus_text``.  The DEFAULT registry first reads the
        process's CPU time and its threads' by role off the process
        (``profiler.read_cpu``: gauges that are read then, not emitted
        on anybody's path) and the trie node store's live nodes off the
        library (``native.read_trie_store``)."""
        if self is DEFAULT:
            # utils/profiler.py has the roles, and imports nothing of
            # this module at import time; crypto/native.py imports
            # nothing of this package
            from eges_tpu.crypto import native
            from eges_tpu.utils import profiler
            profiler.read_cpu(self)
            native.read_trie_store(self)
        with self._lock:
            return sorted(self._metrics.items())

    def snapshot(self) -> dict:
        metrics = self._read()
        out = {}
        for name, m in metrics:
            if isinstance(m, Counter):
                out[name] = m.value
            elif isinstance(m, Gauge):
                out[name] = m.value
            elif isinstance(m, Meter):
                out[name] = {"count": m.count,
                             "rate_mean": round(m.rate_mean, 3),
                             "rate_1m": round(m.rate_1m, 3)}
            elif isinstance(m, Timer):
                out[name] = {"count": m.count,
                             "mean_s": round(m.mean, 6),
                             "min_s": round(m.min, 6) if m.count else 0.0,
                             "max_s": round(m.max, 6)}
            elif isinstance(m, Histogram):
                ps = m.percentiles()
                out[name] = {"count": m.count,
                             "mean": round(m.mean, 6),
                             "min": round(m.min, 6) if m.count else 0.0,
                             "max": round(m.max, 6),
                             "p50": round(ps[50.0], 6),
                             "p95": round(ps[95.0], 6),
                             "p99": round(ps[99.0], 6)}
        return out


# -- Prometheus text exposition (format 0.0.4) --------------------------

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _split_labels(name: str) -> tuple[str, dict[str, str]]:
    """``family;k=v,k2=v2`` -> (family, {k: v})."""
    if ";" not in name:
        return name, {}
    family, _, rest = name.partition(";")
    labels = {}
    for pair in rest.split(","):
        if "=" in pair:
            k, _, v = pair.partition("=")
            labels[k.strip()] = v.strip()
    return family, labels


def _prom_name(family: str) -> str:
    name = _NAME_RE.sub("_", family)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    quoted = ",".join(
        '%s="%s"' % (_prom_name(k),
                     str(v).replace("\\", "\\\\").replace('"', '\\"'))
        for k, v in sorted(labels.items()))
    return "{" + quoted + "}"


def _fmt_value(v: float) -> str:
    f = float(v)
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    return repr(f) if f != int(f) else str(int(f))


def prometheus_text(registry: "Registry | None" = None) -> str:
    """Render the registry in Prometheus text format.

    Counters/Meters become ``counter`` families, numeric Gauges become
    ``gauge``, Timers and Histograms become ``summary`` families (with
    quantile samples for Histograms).  Non-numeric gauges (e.g.
    ``verifier.device_name``) become ``<name>_info{value="..."} 1``.
    """
    reg = registry if registry is not None else DEFAULT
    metrics = reg._read()

    families: dict[str, list[tuple[str, dict, object]]] = {}
    for name, m in metrics:
        family, labels = _split_labels(name)
        families.setdefault(_prom_name(family), []).append((name, labels, m))

    lines: list[str] = []
    for fam in sorted(families):
        members = families[fam]
        kind = type(members[0][2])
        # ``# HELP`` text keyed by the ORIGINAL (dotted) family name of
        # the first member; escaping per exposition format 0.0.4
        help_text = METRIC_HELP.get(_split_labels(members[0][0])[0], "")
        help_text = help_text.replace("\\", "\\\\").replace("\n", "\\n")

        def _help(suffix: str = "") -> None:
            if help_text:
                lines.append(f"# HELP {fam}{suffix} {help_text}")

        if kind is Counter:
            _help()
            lines.append(f"# TYPE {fam} counter")
            for _, labels, m in members:
                lines.append(f"{fam}{_fmt_labels(labels)} "
                             f"{_fmt_value(m.value)}")
        elif kind is Gauge:
            numeric = [(lb, m) for _, lb, m in members
                       if isinstance(m.value, (int, float))]
            info = [(lb, m) for _, lb, m in members
                    if not isinstance(m.value, (int, float))]
            if numeric:
                _help()
                lines.append(f"# TYPE {fam} gauge")
                for labels, m in numeric:
                    lines.append(f"{fam}{_fmt_labels(labels)} "
                                 f"{_fmt_value(m.value)}")
            if info:
                _help("_info")
                lines.append(f"# TYPE {fam}_info gauge")
                for labels, m in info:
                    lb = dict(labels)
                    lb["value"] = str(m.value)
                    lines.append(f"{fam}_info{_fmt_labels(lb)} 1")
        elif kind is Meter:
            _help("_total")
            lines.append(f"# TYPE {fam}_total counter")
            for _, labels, m in members:
                lines.append(f"{fam}_total{_fmt_labels(labels)} {m.count}")
            _help("_rate_1m")
            lines.append(f"# TYPE {fam}_rate_1m gauge")
            for _, labels, m in members:
                lines.append(f"{fam}_rate_1m{_fmt_labels(labels)} "
                             f"{_fmt_value(m.rate_1m)}")
        elif kind is Timer:
            _help()
            lines.append(f"# TYPE {fam} summary")
            for _, labels, m in members:
                lb = _fmt_labels(labels)
                lines.append(f"{fam}_count{lb} {m.count}")
                lines.append(f"{fam}_sum{lb} {_fmt_value(m.total)}")
        elif kind is Histogram:
            _help()
            lines.append(f"# TYPE {fam} summary")
            for _, labels, m in members:
                ps = m.percentiles()
                for q, key in ((50.0, "0.5"), (95.0, "0.95"), (99.0, "0.99")):
                    qlb = dict(labels)
                    qlb["quantile"] = key
                    lines.append(f"{fam}{_fmt_labels(qlb)} "
                                 f"{_fmt_value(ps[q])}")
                qlb = dict(labels)
                qlb["quantile"] = "1"
                mx = m.max if m.count else 0.0
                lines.append(f"{fam}{_fmt_labels(qlb)} {_fmt_value(mx)}")
                lb = _fmt_labels(labels)
                lines.append(f"{fam}_count{lb} {m.count}")
                lines.append(f"{fam}_sum{lb} {_fmt_value(m.total)}")
    return "\n".join(lines) + "\n"


DEFAULT = Registry()
