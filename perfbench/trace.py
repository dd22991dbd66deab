"""From a profiler trace to device busy time, idle gaps and per-program
device time.  ``load`` turns an ``.xplane.pb`` into plain event rows (the
only step that needs jax); everything after it is arithmetic on those rows,
tested on a small recorded trace (``tests/data/trace_small.json``).

An event row is ``[plane, line, name, start_ns, duration_ns]``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host events that say nothing about what the host was doing
_NOISE = re.compile(r"^(ThreadpoolListener|\$threading\.py|\$<unknown>)")
NO_HOST_SPAN = "between_device_windows"
MIN_HOST_NS = 1e6  # a host span shorter than this labels no gap worth listing


def load(trace_dir: str) -> list:
    """Event rows of the newest ``.xplane.pb`` under ``trace_dir``: the
    device planes whole, host planes without their shortest events."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not files:
        return []
    rows = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.duration_ns >= MIN_HOST_NS:
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def _union(intervals: list) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_ops(rows: list) -> dict:
    """plane -> its operation events: the ``XLA Ops`` line where the
    plane has one, else every line but the modules' and steps'."""
    planes: dict = {}
    for p, line, name, s, d in rows:
        if DEVICE_PLANE.match(p):
            planes.setdefault(p, {}).setdefault(line, []).append((name, s, d))
    out = {}
    for p, lines in planes.items():
        if OPS_LINE in lines:
            out[p] = lines[OPS_LINE]
        else:
            out[p] = [e for ln, evs in lines.items()
                      if ln not in (MODULES_LINE, "Steps") for e in evs]
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def short_name(name: str) -> str:
    """An operation as the breakdown lists it: the profiler prints the
    whole HLO instruction; its result's name and first shape tell the
    kernels and the buckets apart."""
    m = _HLO.match(name)
    return f"{m[1]} {m[2]}" if m else name[:80]


def _label(gap, host: list) -> str:
    """What the host was doing in ``gap``: the shortest host span that
    covers at least half of it."""
    gs, ge = gap
    best = None
    for name, s, d in host:
        cover = min(ge, s + d) - max(gs, s)
        if cover * 2 >= ge - gs and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else NO_HOST_SPAN


def span_s(rows: list) -> float:
    """From the first event's start to the last event's end, in seconds:
    the length of a trace whose start and stop this process did not see."""
    return (max(s + d for *_, s, d in rows)
            - min(s for *_, s, _d in rows)) / 1e9 if rows else 0.0


def reduce(rows: list, window_s: float | None, *,
           program: str = "") -> dict | None:
    """``busy_s`` (union of device-operation intervals, averaged over the
    device planes), ``window_s`` as given (the trace's own span where
    None is given), the ten device operations that
    took most time, the ten longest idle gaps, each labelled by the host
    span that covers it, and ``program_s``: the device time of the
    programs whose name matches ``program`` (the modules' line where there
    is one, else the matching operations).  None without device events."""
    ops = _device_ops(rows)
    if not ops or not any(ops.values()):
        return None
    if window_s is None:
        window_s = span_s(rows)
    host = [(n, s, d) for p, _ln, n, s, d in rows
            if not DEVICE_PLANE.match(p) and not _NOISE.match(n)]
    busy, by_name, gaps = [], {}, []
    for plane, evs in ops.items():
        merged = _union([(s, s + d) for _n, s, d in evs if d > 0])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, _s, d in evs:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + d / 1e9
        gaps += [(b[0] - a[1], (a[1], b[0]))
                 for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    out = {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "device_ops": [[n, t] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_label(g, host), t / 1e9] for t, g in gaps[:10]],
    }
    if program:
        pat = re.compile(program)
        mods = [d for p, ln, n, _s, d in rows if DEVICE_PLANE.match(p)
                and ln == MODULES_LINE and pat.search(n)]
        if not mods:
            mods = [d for evs in ops.values() for n, _s, d in evs
                    if pat.search(n)]
        out["program_s"] = sum(mods) / 1e9 / len(ops) if mods else None
    return out


def main(argv) -> int:
    """``python3 -m perfbench.trace <trace dir> <out.json> <window_s|span>
    <program>``: the reduction of a trace as JSON, for a parent that may
    not import jax."""
    out = reduce(load(argv[1]), None if argv[3] == "span" else
                 float(argv[3]), program=argv[4])
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
