"""Device-efficiency observatory: goodput, roofline, HBM, traces.

Role: the device-side half of the observability plane.  PR 16's
sampling profiler (``utils/profiler.py``) answers "which host
*functions* burn the time"; this module answers the symmetric device
question — "how many of the rows we paid device time for were useful,
and how close is each lane to the measured ceiling".  Three surfaces:

* the **goodput ledger** — every recorded scheduler window already
  knows its rows, padded bucket, cache-served/deduped companions and
  hedge outcome (``crypto/scheduler.py`` ``_record_window`` + the
  flight recorder).  :class:`GoodputLedger` folds those into per-lane,
  per-bucket counters whose headline is ``goodput_ratio`` = useful
  rows / padded device rows, and — anchored to the captured TPU bench
  in ``BENCH_tpu_capture.json`` — ``fraction_of_roofline`` = achieved
  rows/s / the per-bucket ceiling parsed from the capture's scaling
  note.

* **HBM/memory telemetry** — :func:`sample_memory` reads per-device
  ``memory_stats()`` watermarks (bytes-in-use, peak, limit) and
  publishes them as ``devstats.mem_*;device=N`` gauges the
  ``RegistrySampler`` tick picks up automatically.  Backends without
  the API (CPU devices return ``None``) degrade to *absent*, never to
  fake zeros.

* **on-demand device traces** — :class:`DeviceTraceArmer` arms a
  ``jax.profiler`` capture for the next N recorded windows (the
  ``thw_device_trace`` RPC), landing a versioned ``device_trace.NNN``
  artifact next to ``profile.folded``.

Determinism contract: like the profiler plane, only aggregate *count*
deltas are journaled — one ``device_efficiency`` event per device per
tick, into a dedicated ``"devstats"`` stream in sims (the chaos
determinism checks never enable it).  Live-push and ``--replay``
collector folds therefore agree byte-for-byte on everything derived
from counts; memory watermarks ride the events as point-in-time
readings and are absent on host-only runs.  Nothing in this module
reads a wall clock — rates come from journaled event timestamps.

Reference: geth ships the memory half as ``debug_memStats`` /
``metrics`` module gauges; the reference repo's ``grep.py`` throughput
loop is the manual ancestor of the roofline fraction reported here.
"""

from __future__ import annotations

import os
import re
import sys
import threading
from collections import deque

SNAP_RING = 64          # delta snapshots kept for the thw_devices RPC
ROOFLINE_FILE = "BENCH_tpu_capture.json"

# repo root, resolved relative to this file (eges_tpu/utils/ -> repo)
_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# The closed per-device counter vocabulary: journaled verbatim in each
# device_efficiency event and summed verbatim by the assembler, so the
# two ends cannot drift.
_COUNTERS = (
    "windows", "rows", "bucket_rows", "cache_rows", "dedup_rows",
    "diverted_windows", "diverted_rows",
    "hedge_windows", "hedge_wasted_windows", "hedge_wasted_rows",
)

# -- roofline anchoring ---------------------------------------------------

# the capture's free-text scaling row: "... 3.7k/s @256, 12.9k/s @1024
# (p50 79.8 ms), 33.5k/s @4096, 54.3k/s @16384"
_SCALING_RE = re.compile(r"(\d+(?:\.\d+)?)k/s\s*@(\d+)")
_ROOFLINE_CACHE: dict[str, dict] = {}


def load_roofline(path: str | None = None) -> dict:
    """Per-bucket device ceilings (rows/s) from the captured TPU bench.

    The scaling row is parsed out of the capture's free-text ``note``
    and the headline ``value``/``batch`` pair overrides its own
    (note-rounded) bucket.  Returns ``{"source", "ceilings"}`` where
    ``ceilings`` maps bucket -> rows/s; empty when the capture is
    missing or unparseable — fraction-of-roofline simply goes
    unreported rather than anchoring to a guess."""
    import json

    if path is None:
        path = os.path.join(_REPO, ROOFLINE_FILE)
    cached = _ROOFLINE_CACHE.get(path)
    if cached is not None:
        return cached
    ceilings: dict[int, float] = {}
    try:
        with open(path, encoding="utf-8") as fh:
            cap = json.load(fh)
        for num, bucket in _SCALING_RE.findall(str(cap.get("note", ""))):
            ceilings[int(bucket)] = float(num) * 1000.0
        batch, value = cap.get("batch"), cap.get("value")
        if isinstance(batch, int) and isinstance(value, (int, float)):
            # the headline number is exact; the note rounds it
            ceilings[batch] = float(value)
    # analysis: allow-swallow(a missing/unparseable capture just disables roofline anchoring)
    except Exception:
        ceilings = {}
    out = {"source": os.path.basename(path), "ceilings": ceilings}
    _ROOFLINE_CACHE[path] = out
    return out


def roofline_ceiling(ceilings: dict[int, float],
                     bucket: int) -> float | None:
    """The rows/s ceiling for one bucket: exact when captured,
    log2-interpolated between captured buckets (throughput scales with
    log batch on the measured curve), linearly scaled below the
    smallest capture, clamped at the largest (the chip does not get
    faster past its peak batch)."""
    import math

    if not ceilings or bucket <= 0:
        return None
    exact = ceilings.get(bucket)
    if exact is not None:
        return exact
    pts = sorted(ceilings.items())
    b0, c0 = pts[0]
    if bucket < b0:
        return c0 * bucket / b0
    bn, cn = pts[-1]
    if bucket > bn:
        return cn
    for (lo, clo), (hi, chi) in zip(pts, pts[1:]):
        if lo < bucket < hi:
            t = ((math.log2(bucket) - math.log2(lo))
                 / (math.log2(hi) - math.log2(lo)))
            return clo + t * (chi - clo)
    return None


# -- on-demand device traces ----------------------------------------------

class DeviceTraceArmer:
    """Arms a ``jax.profiler`` device trace for the next N *recorded*
    windows.  ``step()`` is called once per recorded scheduler window
    (via :meth:`GoodputLedger.observe_window`); the first armed window
    starts the capture, the last one stops it, and the artifact lands
    as a versioned ``device_trace.NNN`` directory next to
    ``profile.folded`` (``dir`` is set by ``NodeService.start`` to the
    datadir; a tempdir otherwise).  Without jax the armer degrades to
    an ``error:*`` state instead of tracing — arming is always safe."""

    def __init__(self):
        self._lock = threading.Lock()
        # artifact directory; set by the node service, else tempdir
        self.dir: str | None = None
        # guarded-by: _lock
        self._remaining = 0
        # guarded-by: _lock
        self._active = False
        # guarded-by: _lock
        self._captures = 0
        # guarded-by: _lock  (idle | armed | tracing | captured | error:*)
        self._state = "idle"
        # guarded-by: _lock
        self._path: str | None = None

    def arm(self, windows: int, outdir: str | None = None) -> dict:
        """Arm a capture spanning the next ``windows`` recorded
        windows (already clamped by the RPC layer); returns status."""
        windows = max(1, int(windows))
        with self._lock:
            if outdir:
                self.dir = str(outdir)
            self._remaining = windows
            if not self._active:
                self._state = "armed"
        return self.status()

    def disarm(self) -> dict:
        """Cancel the armed window count; an in-flight capture stops
        (and counts as captured — the artifact is real)."""
        captured = False
        with self._lock:
            self._remaining = 0
            if self._active:
                captured = self._stop_locked()
            else:
                self._state = "idle"
        if captured:
            from eges_tpu.utils.metrics import DEFAULT as metrics
            metrics.counter("devstats.trace_captures").inc()
        return self.status()

    def step(self) -> None:
        """One recorded window elapsed — start/advance/stop the
        capture as armed.  Cheap no-op (one lock round) when idle, so
        it sits on the window-recording path safely."""
        captured = False
        with self._lock:
            if self._remaining <= 0 and not self._active:
                return
            if not self._active and self._remaining > 0:
                self._start_locked()
            if self._active:
                self._remaining -= 1
                if self._remaining <= 0:
                    captured = self._stop_locked()
        if captured:
            from eges_tpu.utils.metrics import DEFAULT as metrics
            metrics.counter("devstats.trace_captures").inc()

    def _start_locked(self) -> None:
        # lazy import: the hot path never pays for jax.profiler unless
        # a capture is actually armed
        try:
            from jax import profiler as jax_profiler
            import tempfile

            base = self.dir or tempfile.gettempdir()
            path = os.path.join(base,
                                "device_trace.%03d" % self._captures)
            os.makedirs(path, exist_ok=True)
            # the program's own spans (utils/tracing.py) say what the
            # Python tracer's frame names said, at a fraction of the
            # export: a trace with it on froze the node for half a
            # minute after five traced seconds
            opts = jax_profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax_profiler.start_trace(path, profiler_options=opts)
        # analysis: allow-swallow(backends without jax.profiler report an error state instead of tracing)
        except Exception as exc:
            self._remaining = 0
            self._state = f"error:{type(exc).__name__}"
            return
        self._active = True
        self._path = path
        self._state = "tracing"

    def _stop_locked(self) -> bool:
        try:
            from jax import profiler as jax_profiler

            jax_profiler.stop_trace()
        # analysis: allow-swallow(a failed trace stop leaves the error visible in the armer state)
        except Exception as exc:
            self._active = False
            self._state = f"error:{type(exc).__name__}"
            return False
        self._active = False
        self._captures += 1
        self._state = "captured"
        return True

    def status(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "armed_windows": self._remaining,
                "active": self._active,
                "captures": self._captures,
                "path": self._path,
                "dir": self.dir,
            }


# -- the goodput ledger ---------------------------------------------------

class GoodputLedger:
    """Per-device window/row accounting fed by the scheduler's
    ``_record_window`` tail.  Counters only — no wall clock, no stacks
    — so the journaled deltas sit inside the determinism contract the
    collector fold relies on."""

    def __init__(self, *, snapshots: int = SNAP_RING):
        self._lock = threading.Lock()
        # guarded-by: _lock  (device -> cumulative counter dict)
        self._dev: dict[int, dict] = {}
        # guarded-by: _lock  ((device, bucket) -> [windows, rows, bucket_rows])
        self._buckets: dict[tuple[int, int], list[int]] = {}
        # guarded-by: _lock  (delta baselines for snap())
        self._base_dev: dict[int, dict] = {}
        # guarded-by: _lock
        self._base_buckets: dict[tuple[int, int], list[int]] = {}
        # guarded-by: _lock  (latest memory_stats watermarks per device)
        self._mem: dict[int, dict] = {}
        # guarded-by: _lock
        self._snaps: deque[dict] = deque(maxlen=max(1, snapshots))
        # guarded-by: _lock
        self._snap_seq = 0
        self.trace = DeviceTraceArmer()

    def _dev_locked(self, device: int) -> dict:
        d = self._dev.get(device)
        if d is None:
            d = {k: 0 for k in _COUNTERS}
            self._dev[device] = d
        return d

    # -- ingestion (scheduler hooks) --------------------------------------
    def observe_window(self, device: int, rows: int, bucket: int, *,
                       cache_rows: int = 0, dedup_rows: int = 0,
                       diverted: bool = False,
                       hedged: bool = False) -> None:  # hot-path-entry
        """One recorded (winner) scheduler window.  Host-served windows
        (by rule, and breaker/straggler diverts) never padded a
        device bucket, so their rows stay out of the goodput
        denominator and land in the ``diverted_rows`` rescue column
        instead."""
        device, rows, bucket = int(device), int(rows), int(bucket)
        with self._lock:
            d = self._dev_locked(device)
            d["windows"] += 1
            d["cache_rows"] += int(cache_rows)
            d["dedup_rows"] += int(dedup_rows)
            if hedged:
                d["hedge_windows"] += 1
            if diverted:
                d["diverted_windows"] += 1
                d["diverted_rows"] += rows
            else:
                d["rows"] += rows
                d["bucket_rows"] += bucket
                bk = self._buckets.get((device, bucket))
                if bk is None:
                    bk = [0, 0, 0]
                    self._buckets[(device, bucket)] = bk
                bk[0] += 1
                bk[1] += rows
                bk[2] += bucket
        self.trace.step()

    def observe_hedge_waste(self, device: int, rows: int,
                            bucket: int) -> None:
        """A hedge LOSER ran a full padded window the winner made
        redundant — pure device waste, billed at the padded size."""
        with self._lock:
            d = self._dev_locked(int(device))
            d["hedge_wasted_windows"] += 1
            d["hedge_wasted_rows"] += int(bucket)

    def note_memory(self, by_device: dict) -> None:
        """Stash the latest :func:`sample_memory` watermarks so the
        next journaled delta carries them."""
        with self._lock:
            for dev, rec in by_device.items():
                self._mem[int(dev)] = dict(rec)

    # -- snapshots --------------------------------------------------------
    def _rebase_locked(self) -> None:
        self._base_dev = {d: dict(v) for d, v in self._dev.items()}
        self._base_buckets = {k: list(v)
                              for k, v in self._buckets.items()}

    def rebase(self) -> None:
        """Reset the delta baseline to the current totals WITHOUT
        recording a snapshot — called when a sim or the node service
        enables the plane, so windows recorded by earlier runs in the
        same process never leak into the first tick (the
        ``RegistrySampler`` baseline-at-attach discipline)."""
        with self._lock:
            self._rebase_locked()

    def snap(self) -> dict:
        """One delta report since the previous ``snap()`` — per-device
        counters plus their per-bucket split, the unit the
        ``thw_devices`` RPC pages through and the sim devstats plane
        journals.  Appended to a bounded ring."""
        with self._lock:
            devices: dict[int, dict] = {}
            for dev in sorted(self._dev):
                cur = self._dev[dev]
                base = self._base_dev.get(dev, {})
                delta = {k: cur[k] - base.get(k, 0) for k in _COUNTERS}
                if not any(delta.values()):
                    continue
                buckets: dict[str, list[int]] = {}
                for (bdev, bucket), bk in self._buckets.items():
                    if bdev != dev:
                        continue
                    bb = self._base_buckets.get((bdev, bucket),
                                                (0, 0, 0))
                    row = [bk[0] - bb[0], bk[1] - bb[1], bk[2] - bb[2]]
                    if any(row):
                        buckets[str(bucket)] = row
                delta["buckets"] = {k: buckets[k]
                                    for k in sorted(buckets, key=int)}
                mem = self._mem.get(dev)
                if mem:
                    delta["mem"] = dict(mem)
                devices[dev] = delta
            snap = {
                "seq": self._snap_seq,
                "devices": {str(d): devices[d] for d in sorted(devices)},
            }
            self._snap_seq += 1
            self._rebase_locked()
            self._snaps.append(snap)
            ratios = {d: (v["rows"], v["bucket_rows"])
                      for d, v in devices.items() if v["bucket_rows"]}
        # emitted after release: gauges take the registry lock
        from eges_tpu.utils.metrics import DEFAULT as metrics
        for dev, (r, br) in ratios.items():
            metrics.gauge(f"devstats.goodput_ratio;device={dev}") \
                .set(round(r / br, 4))
        return snap

    def snapshots(self, limit: int = 0) -> list[dict]:
        """Oldest-first delta snapshots (RPC callers reverse for the
        newest-first wire contract, like ``thw_profile``)."""
        with self._lock:
            out = list(self._snaps)
        if limit and limit > 0:
            out = out[-limit:]
        return out

    def journal_snapshot(self, journal) -> int:
        """Take a :meth:`snap` and journal one ``device_efficiency``
        event PER device with a non-empty delta, in device order (so
        event order is deterministic).  Returns the number of events
        recorded; an all-idle tick records nothing — unlike
        ``profiler_report`` there is no meaningful empty payload."""
        snap = self.snap()
        n = 0
        for dev_str, d in snap["devices"].items():
            attrs = {k: d[k] for k in _COUNTERS}
            attrs["device"] = int(dev_str)
            attrs["pad_rows"] = d["bucket_rows"] - d["rows"]
            attrs["buckets"] = d["buckets"]
            mem = d.get("mem")
            if mem:
                # point-in-time HBM watermarks ride the count event but
                # are volatile by nature; absent on backends without
                # memory_stats() (the CPU fallback stays green)
                attrs["mem"] = mem
            journal.record("device_efficiency", **attrs)
            n += 1
        return n

    def stats(self) -> dict:
        """The ``thw_health`` block: cumulative volume, goodput, trace
        armer state."""
        with self._lock:
            windows = sum(d["windows"] for d in self._dev.values())
            rows = sum(d["rows"] for d in self._dev.values())
            bucket_rows = sum(d["bucket_rows"]
                              for d in self._dev.values())
            snaps = len(self._snaps)
            mem_devices = len(self._mem)
            ndev = len(self._dev)
        return {
            "devices": ndev,
            "windows": windows,
            "rows": rows,
            "bucket_rows": bucket_rows,
            "goodput_ratio": (round(rows / bucket_rows, 4)
                              if bucket_rows else None),
            "snapshots": snaps,
            "mem_devices": mem_devices,
            "trace": self.trace.status(),
        }


# The process-wide ledger the scheduler feeds and the RPC/health
# surfaces read.  NOT baselined here — sims and the node service call
# rebase() when they enable the plane.
DEFAULT = GoodputLedger()


# -- HBM/memory telemetry -------------------------------------------------

def sample_memory(ledger: GoodputLedger | None = None,
                  devices=None) -> dict:
    """Read per-device ``memory_stats()`` watermarks and publish them
    as ``devstats.mem_*;device=N`` gauges (the ``RegistrySampler``
    tick then carries them in every ``telemetry_sample``).  Degrades
    to ``{}`` — publishing nothing — when jax was never imported, has
    no devices, or the backend lacks the API (CPU devices return
    ``None``): the host fallback stays green by being absent, not by
    faking zeros.  Never imports jax itself: if nothing else in the
    process paid the import cost, there is no device to meter."""
    led = DEFAULT if ledger is None else ledger
    if devices is None:
        jx = sys.modules.get("jax")
        if jx is None:
            return {}
        try:
            devices = jx.devices()
        # analysis: allow-swallow(an uninitializable backend means no devices to meter)
        except Exception:
            return {}
    out: dict[int, dict] = {}
    from eges_tpu.utils.metrics import DEFAULT as metrics
    for i, dev in enumerate(devices):
        fn = getattr(dev, "memory_stats", None)
        if not callable(fn):
            continue
        try:
            ms = fn()
        # analysis: allow-swallow(a backend erroring on memory_stats simply has no watermarks)
        except Exception:
            continue
        if not isinstance(ms, dict):
            continue  # CPU backends return None: no watermarks
        rec: dict[str, int] = {}
        val = ms.get("bytes_in_use")
        if val is not None:
            rec["bytes_in_use"] = int(val)
            metrics.gauge(f"devstats.mem_bytes_in_use;device={i}") \
                .set(int(val))
        val = ms.get("peak_bytes_in_use")
        if val is not None:
            rec["peak_bytes"] = int(val)
            metrics.gauge(f"devstats.mem_peak_bytes;device={i}") \
                .set(int(val))
        val = ms.get("bytes_limit")
        if val is not None:
            rec["limit_bytes"] = int(val)
            metrics.gauge(f"devstats.mem_limit_bytes;device={i}") \
                .set(int(val))
        if rec:
            out[i] = rec
    if out:
        led.note_memory(out)
    return out


# -- collector-plane assembler --------------------------------------------

class DevstatsAssembler:
    """Incremental fold of journaled ``device_efficiency`` events into
    one cluster-wide device-efficiency report — the devstats analog of
    ``ProfileAssembler``.  Pure function of the event stream, so the
    live-push and ``--replay`` collector paths agree byte-for-byte on
    everything derived from counts."""

    def __init__(self):
        self._nodes: dict[str, int] = {}
        self._dev: dict[int, dict] = {}
        self._buckets: dict[tuple[int, int], list[int]] = {}
        self._mem: dict[int, dict] = {}
        self._first_ts: dict[int, float] = {}
        self._last_ts: dict[int, float] = {}

    def ingest(self, ev: dict) -> None:
        if ev.get("type") != "device_efficiency":
            return
        node = str(ev.get("node", "?"))
        self._nodes[node] = self._nodes.get(node, 0) + 1
        dev = int(ev.get("device", 0) or 0)
        d = self._dev.get(dev)
        if d is None:
            d = {k: 0 for k in _COUNTERS}
            self._dev[dev] = d
        for k in _COUNTERS:
            d[k] += int(ev.get(k, 0) or 0)
        for bucket_s, row in (ev.get("buckets") or {}).items():
            key = (dev, int(bucket_s))
            bk = self._buckets.get(key)
            if bk is None:
                bk = [0, 0, 0]
                self._buckets[key] = bk
            bk[0] += int(row[0])
            bk[1] += int(row[1])
            bk[2] += int(row[2])
        mem = ev.get("mem")
        if isinstance(mem, dict):
            # last write wins — the collector feeds events in
            # (ts, node, seq) order, so this is the newest watermark
            self._mem[dev] = dict(mem)
        ts = ev.get("ts")
        if isinstance(ts, (int, float)):
            self._first_ts.setdefault(dev, float(ts))
            self._last_ts[dev] = float(ts)
        from eges_tpu.utils.metrics import DEFAULT as metrics
        metrics.counter("devstats.reports").inc()

    def report(self) -> dict:
        roof = load_roofline()
        ceilings = roof["ceilings"]
        devices: dict[str, dict] = {}
        for dev in sorted(self._dev):
            d = self._dev[dev]
            span = (self._last_ts.get(dev, 0.0)
                    - self._first_ts.get(dev, 0.0))
            rows_per_s = (round(d["rows"] / span, 3)
                          if span > 0 and d["rows"] else None)
            buckets: dict[str, dict] = {}
            for (bdev, bucket) in sorted(self._buckets):
                if bdev != dev:
                    continue
                w, r, br = self._buckets[(bdev, bucket)]
                ceil = roofline_ceiling(ceilings, bucket)
                buckets[str(bucket)] = {
                    "windows": w, "rows": r, "bucket_rows": br,
                    "goodput_ratio": round(r / br, 4) if br else None,
                    "ceiling_rows_per_s": (round(ceil, 1)
                                           if ceil else None),
                }
            entry = {k: d[k] for k in _COUNTERS}
            entry["pad_rows"] = d["bucket_rows"] - d["rows"]
            entry["goodput_ratio"] = (round(d["rows"] / d["bucket_rows"],
                                            4)
                                      if d["bucket_rows"] else None)
            entry["rows_per_s"] = rows_per_s
            # achieved rows/s against the ceiling of the device's
            # row-weighted mean bucket — the single-number headline the
            # per-bucket table decomposes
            frac = None
            dev_windows = d["windows"] - d["diverted_windows"]
            if rows_per_s and dev_windows > 0 and d["bucket_rows"]:
                ceil = roofline_ceiling(
                    ceilings, round(d["bucket_rows"] / dev_windows))
                if ceil:
                    frac = round(rows_per_s / ceil, 4)
            entry["fraction_of_roofline"] = frac
            entry["buckets"] = buckets
            if dev in self._mem:
                entry["mem"] = self._mem[dev]
            devices[str(dev)] = entry
        tot = {k: sum(d[k] for d in self._dev.values())
               for k in _COUNTERS}
        tot["pad_rows"] = tot["bucket_rows"] - tot["rows"]
        tot["goodput_ratio"] = (round(tot["rows"] / tot["bucket_rows"], 4)
                                if tot["bucket_rows"] else None)
        return {
            "reports": sum(self._nodes.values()),
            "nodes": {k: self._nodes[k] for k in sorted(self._nodes)},
            "roofline_source": roof["source"] if ceilings else None,
            "totals": tot,
            # where potential device rows went instead of useful work:
            # padding burned, cache served free, dedup merged, hedge
            # losers burned, host rescues
            "waste": {
                "pad_rows": tot["pad_rows"],
                "cache_rows": tot["cache_rows"],
                "dedup_rows": tot["dedup_rows"],
                "hedge_wasted_rows": tot["hedge_wasted_rows"],
                "diverted_rows": tot["diverted_rows"],
            },
            "devices": devices,
        }


def assemble(by_node: dict[str, list[dict]]) -> dict:
    """Batch-mode fold over per-stream event lists (the observatory
    ``--replay`` path); mirrors ``profiler.assemble``."""
    from harness.collector import _order_key  # analysis: allow-layer-violation(selftest assembles sim journals; not a runtime dependency)

    asm = DevstatsAssembler()
    merged: list[dict] = []
    for events in by_node.values():
        merged.extend(e for e in events
                      if e.get("type") == "device_efficiency")
    merged.sort(key=_order_key)
    for ev in merged:
        asm.ingest(ev)
    return asm.report()


# -- selftest (the `make devstats` smoke) ---------------------------------

def _selftest() -> int:
    """Sim smoke: run a 4-node sim on a 2-lane JAX-free host mesh with
    the devstats plane enabled, then assert the journaled
    ``device_efficiency`` events reassemble into a consistent goodput
    report anchored to the captured roofline."""
    from eges_tpu.sim.cluster import SimCluster  # analysis: allow-layer-violation(selftest drives a sim cluster; not a runtime dependency)

    roof = load_roofline()
    assert roof["ceilings"], "roofline scaling row failed to parse"
    assert roof["ceilings"][16384] == 54296.9, roof["ceilings"]
    assert roof["ceilings"][256] == 3700.0, roof["ceilings"]
    mid = roofline_ceiling(roof["ceilings"], 2048)
    lo, hi = roof["ceilings"][1024], roof["ceilings"][4096]
    assert lo < mid < hi, (lo, mid, hi)

    cluster = SimCluster(4, seed=0, txn_per_block=4, txpool=True,
                         mesh_devices=2)
    cluster.enable_devstats(interval_s=1.0)
    cluster.start()
    cluster.run(600.0, stop_condition=lambda: cluster.min_height() >= 3)
    assert cluster.min_height() >= 3, cluster.heights()
    for sn in cluster.nodes:
        sn.node.stop()
    cluster.stop_devstats()

    events = cluster.journals().get("devstats", [])
    assert events, "no device_efficiency events journaled"
    rep = assemble({"devstats": events})
    tot = rep["totals"]
    assert tot["windows"] > 0, tot
    assert tot["rows"] > 0, tot
    assert tot["bucket_rows"] >= tot["rows"], tot
    gp = tot["goodput_ratio"]
    assert gp is not None and 0.0 < gp <= 1.0, tot
    # the per-bucket split sums back to the device totals
    for entry in rep["devices"].values():
        assert sum(b["rows"] for b in entry["buckets"].values()) \
            == entry["rows"], entry
        assert sum(b["bucket_rows"] for b in entry["buckets"].values()) \
            == entry["bucket_rows"], entry
    # read the CANONICAL module's ledger: under ``python -m`` this file
    # is also loaded as ``__main__``, and the scheduler feeds the
    # ``eges_tpu.utils.devstats`` instance, not this shadow copy
    from eges_tpu.utils import devstats as _canon
    st = _canon.DEFAULT.stats()
    assert st["windows"] >= tot["windows"], (st, tot)
    # analysis: allow-print(CLI selftest verdict for make check)
    print(f"devstats selftest OK: windows={tot['windows']} "
          f"rows={tot['rows']} goodput={gp} "
          f"devices={sorted(rep['devices'])} "
          f"roofline={rep['roofline_source']}")
    return 0


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="device-efficiency observatory utilities")
    ap.add_argument("--selftest", action="store_true",
                    help="run the simulated 2-lane mesh smoke")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
