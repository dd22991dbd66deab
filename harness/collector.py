"""Streaming cluster telemetry collector.

Replaces per-node ``/metrics`` polling for cluster views: every node
pushes envelopes — its journal tail including periodic
``telemetry_sample`` events (see ``eges_tpu/utils/timeseries.py``) —
and the :class:`ClusterCollector` folds them into live per-cluster
series plus a burn-rate SLO evaluation (``harness/slo.py``).

Determinism contract (the round-trip test's byte-match): the collector
is a PURE incremental function over the per-node event streams.  Events
buffer until the next ``telemetry_sample`` barrier, flush in sorted
``(ts, node, seq, type)`` order, and the SLO engine evaluates exactly
once per sample at the sample's timestamp — so live envelope ingestion
(simulator push channel) and an offline journal replay
(:meth:`ClusterCollector.replay`) reconstruct byte-identical reports.

Real deployments use :class:`CollectorServer`, a line-oriented TCP
endpoint ``node/service.py`` pushes JSON envelopes to; simulated
clusters wire ``SimCluster.enable_telemetry(sink=collector.ingest)``
so delivery rides the virtual clock.
"""

from __future__ import annotations

import json
import socket
import threading

from eges_tpu.utils.metrics import DEFAULT as metrics
from eges_tpu.utils.timeseries import SeriesStore, fold_payload
from eges_tpu.utils.ledger import LedgerAssembler
from eges_tpu.utils.devstats import DevstatsAssembler
from eges_tpu.utils.profiler import ProfileAssembler
from harness.anatomy import AnatomyAssembler
from harness.slo import SLOEngine


def _order_key(ev: dict) -> tuple:  # api: _order_key
    return (float(ev.get("ts", 0.0)), str(ev.get("node", "")),
            int(ev.get("seq", 0)), str(ev.get("type", "")))


class ClusterCollector:
    """Aggregates pushed telemetry envelopes into live cluster series
    and an SLO alert stream.

    An envelope is ``{"node": name, "ts": t, "events": [...]}`` — the
    journal tail a node has not shipped yet.  ``finalize()`` flushes
    events still waiting for a sample barrier; call it before
    :meth:`report`.
    """

    def __init__(self, *, objectives=None, capacity: int = 512,
                 window_points: int = 4096):
        self.store = SeriesStore(capacity)
        kw = {"window_points": window_points}
        if objectives is not None:
            kw["objectives"] = objectives
        self.slo = SLOEngine(**kw)
        # commit-anatomy fold rides the same sorted barrier flush as the
        # SLO engine, so the anatomy section of the report keeps the
        # live/replay byte-identity; firing alerts pull their dominant
        # phase from the state folded so far
        self.anatomy = AnatomyAssembler()
        self.slo.phase_hint = self.anatomy.dominant
        # ingress-provenance fold: same sorted barrier flush, same
        # live/replay byte-identity contract as the anatomy section
        self.ledger = LedgerAssembler()
        # continuous-profiler fold: aggregate profiler_report events
        # (sample counts are deterministic functions of the stream even
        # though the sampled stacks behind them are wall-clock)
        self.profile = ProfileAssembler()
        # device-efficiency fold: per-device device_efficiency count
        # deltas — goodput/waste/roofline are pure functions of the
        # stream, so live push and --replay agree byte-for-byte
        self.devstats = DevstatsAssembler()
        self._buffer: list[dict] = []  # guarded-by: _lock
        self._event_counts: dict[str, int] = {}  # guarded-by: _lock
        self.envelopes = 0  # guarded-by: _lock
        self._last_ts = 0.0  # guarded-by: _lock
        self._lock = threading.Lock()

    # -- ingestion ------------------------------------------------------
    def ingest(self, envelope: dict) -> None:
        if not isinstance(envelope, dict):
            return
        events = envelope.get("events")
        if not isinstance(events, list):
            return
        node = str(envelope.get("node", "?"))
        metrics.counter("telemetry.envelopes").inc()
        with self._lock:
            self.envelopes += 1
            self._event_counts[node] = (
                self._event_counts.get(node, 0) + len(events))
            for ev in events:
                if not isinstance(ev, dict):
                    continue
                ts = float(ev.get("ts", 0.0))
                if ts > self._last_ts:
                    self._last_ts = ts
                if ev.get("type") == "telemetry_sample":
                    self._step(ev, ts)
                else:
                    self._buffer.append(ev)

    def _flush(self, before_ts: float | None) -> None:
        """Feed buffered events with ts strictly below the barrier (all
        of them when ``before_ts`` is None) to the SLO engine in sorted
        order.  Events AT the barrier timestamp wait for the next step,
        which keeps live push order and offline replay order identical
        for same-instant races."""
        if before_ts is None:
            ready, self._buffer = self._buffer, []
        else:
            ready = [e for e in self._buffer
                     if float(e.get("ts", 0.0)) < before_ts]
            self._buffer = [e for e in self._buffer
                            if float(e.get("ts", 0.0)) >= before_ts]
        for ev in sorted(ready, key=_order_key):
            self.anatomy.ingest(ev)
            self.ledger.ingest(ev)
            self.profile.ingest(ev)
            self.devstats.ingest(ev)
            self.slo.ingest(ev)

    def _step(self, sample: dict, ts: float) -> None:
        self._flush(ts)
        payload = sample.get("metrics")
        if isinstance(payload, dict):
            fold_payload(self.store, ts, payload)
        self.slo.ingest(sample)
        self.slo.evaluate(ts)

    def finalize(self) -> None:
        """Flush the tail (events still waiting for a barrier) and run
        one final evaluation at the newest timestamp seen."""
        with self._lock:
            self._flush(None)
            self.slo.evaluate(self._last_ts)

    # -- export ---------------------------------------------------------
    def alerts(self) -> list[dict]:
        return self.slo.alerts()

    def report(self) -> dict:
        """Deterministic aggregate view: per-node event counts, the
        bounded series rings, and the full alert stream + states."""
        with self._lock:
            counts = {k: self._event_counts[k]
                      for k in sorted(self._event_counts)}
        return {
            "nodes": sorted(counts),
            "event_counts": counts,
            "series": self.store.as_dict(),
            "alerts": self.slo.alerts(),
            "alert_states": self.slo.alert_states(),
            "compliance_ratio": round(self.slo.compliance_ratio, 6),
            "alerts_fired": self.slo.fired_total,
            "anatomy": self.anatomy.report(),
            "ledger": self.ledger.report(),
            "profile": self.profile.report(),
            "devstats": self.devstats.report(),
        }

    def report_json(self) -> str:
        return json.dumps(self.report(), sort_keys=True)

    # -- offline reconstruction ----------------------------------------
    @classmethod
    def replay(cls, by_node: dict[str, list[dict]],
               **kwargs) -> "ClusterCollector":
        """Rebuild a collector from per-node journal streams (the shape
        ``SimCluster.journals()`` / ``journal.load`` produce).  The
        ``slo`` stream is the live engine's OUTPUT and is skipped;
        streams carrying ``telemetry_sample`` barriers are fed last so
        barrier flushes see every other stream's events, which makes
        the reconstruction byte-identical to the live ingestion."""
        col = cls(**kwargs)
        names = [n for n in sorted(by_node) if n != "slo"]
        with_samples = [
            n for n in names
            if any(isinstance(e, dict)
                   and e.get("type") == "telemetry_sample"
                   for e in by_node[n])]
        plain = [n for n in names if n not in set(with_samples)]
        for name in plain + with_samples:
            col.ingest({"node": name, "ts": 0.0,
                        "events": by_node[name]})
        col.finalize()
        return col


class CollectorServer:
    """Line-oriented TCP ingest endpoint for real-node telemetry.

    Each connection carries newline-delimited JSON envelopes (the
    format ``node/service.py`` pushes).  ``port=0`` binds an ephemeral
    port; read the bound address from :attr:`address`.
    """

    def __init__(self, collector: ClusterCollector,
                 host: str = "127.0.0.1", port: int = 0):
        self.collector = collector
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.settimeout(1.0)  # bounds accept() so close() can stop us
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((host, port))
        sock.listen(16)
        self._sock = sock
        self.address: tuple[str, int] = sock.getsockname()[:2]
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, name="collector-accept", daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break  # socket closed by close()
            conn.settimeout(10.0)
            threading.Thread(target=self._client, args=(conn,),
                             name="collector-conn", daemon=True).start()

    def _client(self, conn: socket.socket) -> None:
        buf = b""
        try:
            while not self._stop.is_set():
                try:
                    chunk = conn.recv(65536)
                except socket.timeout:
                    continue
                if not chunk:
                    break
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if not line.strip():
                        continue
                    try:
                        env = json.loads(line)
                    except ValueError:
                        continue  # torn line; resync on the next one
                    if isinstance(env, dict):
                        self.collector.ingest(env)
        except OSError:
            pass  # peer reset mid-stream: everything parsed was ingested
        finally:
            conn.close()

    def close(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass  # already closed
        self._thread.join(2.0)
