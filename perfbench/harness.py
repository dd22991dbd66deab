"""What every run shares: finding a cell's files by the names in
``BENCHMARK.json``, the arithmetic of quantiles and counter deltas, the
per-layer readers' dispatch, the checks that decide ``correct`` and the
result's last line."""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))  # the code, the tests' data
# The ONE place the benchmark's data files are found from: BENCHMARK.json,
# configs/, traffic/ and metrics/ are read under ROOT each time they are
# asked for, so a test that points ROOT at a copy of them reads the copy.
ROOT = os.path.dirname(HERE)


def build_native() -> None:
    """Build the program's native library before any module of the program
    is imported.  ``eges_tpu.crypto`` decides AT IMPORT whether the library
    is there, so a process that imports it and then builds stays on the
    pure-Python fallback (about 25 times slower through the ingress path)
    for its whole life; a fresh checkout has no library.  ``native.py`` is
    therefore loaded by its path, alone, and asked to build."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_perfbench_native_build",
        os.path.join(ROOT, "eges_tpu", "crypto", "native.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.ensure_built()  # raises if make fails


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, rehearse: bool):
        self.bench = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(conf["file"])
        self.traffic = load_json("perfbench", "traffic",
                                 self.entry["traffic"] + ".json")
        if rehearse:  # the tiny dress-rehearsal cut, from the same files
            self.config["deployment"].update(self.config.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self._mine(m)]


def metric_file(name: str) -> dict:
    """A per-layer metric's own file: ``metrics/<name>.json``, or the
    file of the name without its last suffix (``sched_wait_ms.lat`` is
    read as ``sched_wait_ms.json`` describes, with the suffix's
    arguments laid over the shared ones)."""
    if os.path.exists(os.path.join(ROOT, "perfbench", "metrics",
                                   name + ".json")):
        return load_json("perfbench", "metrics", name + ".json")
    base, _, suffix = name.rpartition(".")
    spec = load_json("perfbench", "metrics", base + ".json")
    per = spec.get("suffixes", {}).get("." + suffix)
    if per is None:
        raise KeyError(f"{base}.json names no suffix .{suffix}")
    spec["args"] = {**spec.get("args", {}), **per.get("args", {})}
    return spec


def read_per_layer(cell: Cell, obs: dict) -> dict:
    """Each of the cell's per-layer metrics through its reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        spec = metric_file(m["name"])
        reader = importlib.import_module("perfbench.readers."
                                         + spec["reader"])
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- arithmetic -----------------------------------------------------------

def quantile(values, q: float):
    """The q-quantile (0..1) by linear interpolation between order
    statistics; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def pick(snapshot: dict, path: str):
    """``a.b.c`` in a metrics snapshot: the registry's flat dotted names
    first (``verifier.rows``), then nested groups (``scheduler.rows``).
    A meter or histogram counts as its ``count``."""
    v = snapshot.get(path)
    if v is None:
        node = snapshot
        for part in path.split("."):
            if not isinstance(node, dict) or part not in node:
                return None
            node = node[part]
        v = node
    if isinstance(v, dict):
        v = v.get("count")
    return v


def delta(obs: dict, path: str) -> float:
    """A counter's growth over the measured window (absent reads as 0)."""
    return ((pick(obs["after"], path) or 0)
            - (pick(obs["before"], path) or 0))


# -- correct ----------------------------------------------------------------

class Checks:
    """Each number compared, beside its limit."""

    def __init__(self):
        self.rows: list[dict] = []

    def _add(self, name: str, value, rule: str, limit, ok: bool) -> None:
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "rule": rule, "ok": value is not None and ok})

    def at_most(self, name: str, value, limit) -> None:
        self._add(name, value, "<=", limit,
                  value is not None and value <= limit)

    def at_least(self, name: str, value, limit) -> None:
        self._add(name, value, ">=", limit,
                  value is not None and value >= limit)

    def equals(self, name: str, value, limit) -> None:
        self._add(name, value, "==", limit, value == limit)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def finish(cell: Cell, trace: bool, *, end_to_end: dict, obs: dict,
           device: dict, checks: Checks, attempted: int, failed: int,
           breakdown: dict | None = None, rehearse: bool = False) -> int:
    """Print the checks and the result's line; returns the exit code."""
    if trace:
        metrics = read_per_layer(cell, obs)
    else:
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    line = {"correct": checks.correct and not rehearse,
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    if rehearse:
        line["rehearsal"] = True
    line["checks"] = {r["name"]: [r["value"], r["rule"], r["limit"]]
                      for r in checks.rows}
    sys.stdout.flush()
    for r in checks.rows:
        print(f"check {r['name']}: {r['value']} {r['rule']} {r['limit']}"
              f" {'ok' if r['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 1 if rehearse else 0


def sleep_until(t: float) -> float:
    """Sleep to monotonic time ``t``; returns how late the wake-up was."""
    while True:
        now = time.monotonic()
        if now >= t:
            return now - t
        time.sleep(min(t - now, 0.25))
