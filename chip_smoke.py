#!/usr/bin/env python3
"""The served verify path, once, on the chip — the quickest proof that
the system still starts there.

``python chip_smoke.py`` needs one TPU.  It builds the native library
from what git would commit, then runs two phases one after another, each
child the only holder of the chip while it lives:

* ``verifier`` (one child): the objects a node builds —
  ``default_verifier()`` behind ``scheduler_for()`` — recover seeded
  rows in 1024-row windows, and one 16384-row batch goes straight
  through ``BatchVerifier.ecrecover``; a known share of the rows is
  invalid; addresses and the ok-mask are compared row for row with the
  native C++ batch recover, the kernels in isolation with the graph
  ops, and the kernel path with the plain graph path bit for bit.
* ``served`` (node processes, started by ``harness/cluster.py`` from
  this JAX-free parent): upstream's documented 3-node cluster on real
  sockets (1000 txn/block, 100-byte payloads), node 0 holding the chip
  with the JAX verifier and warming every bucket before it serves, the
  others on the native C++ verifier; a few thousand signed transfers
  enter by ``eth_sendRawTransaction`` on the chip node and, through
  node 1, by the gossip path, plus the UDP Geec transactions.

``--chips 4`` runs phase ``mesh`` (one scheduler lane per device, and
the full-mesh sharded recover with its tally, against device 0 alone
and native) and no other phase.

Everything worth printing goes out as one JSON object per line; the
LAST line is ``{"ok": true, "device": {...}}`` with the device as JAX
reported it in a child, and is printed only if every phase passed on a
TPU.  Any failed check raises in its phase and the exit code is
non-zero.  ``--rehearse`` is the CPU dress rehearsal (tiny sizes,
whatever backend JAX has): the device-path checks (platform is a TPU,
kernel path on, interpret off, ``tpu_custom_call`` in the program) are
then recorded instead of raised so the rest of the control flow runs —
and the exit code is still non-zero, because a rehearsal is not a pass.

This parent never imports JAX, nor any module that does.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# sizes: what a deployment uses, and the tiny dress-rehearsal cut
FULL = {"window_rows": 3072, "batch_rows": 16384, "mesh_rows": 4096,
        "sched_max_batch": None, "accounts": 64, "txns_per_account": 256,
        "rpc_batch": 1024, "n_udp": 300, "warm_deadline_s": 900.0,
        "child_timeout_s": 1000.0}
TINY = {"window_rows": 16, "batch_rows": 16, "mesh_rows": 64,
        "sched_max_batch": 64, "accounts": 4, "txns_per_account": 6,
        "rpc_batch": 12, "n_udp": 20, "warm_deadline_s": 900.0,
        "child_timeout_s": 1500.0}
INVALID_EVERY = 8  # one row in eight is invalid, four kinds in turn

# checks that can only hold on the chip; --rehearse records these.  The
# last two are here because the CPU backend's verifier (tens of rows a
# second) cannot keep a node at the head of a chain two native nodes
# mine, so that node never proposes the Geec transactions it was sent,
# and because a rehearsal's two dozen transfers are outnumbered by the
# idle blocks' one-row vote checks, which the scheduler sends to the host.
ONLY_ON_CHIP = frozenset({"platform_is_tpu", "ladder_kernels_enabled",
                          "interpret_off", "tpu_custom_call_in_program",
                          "device_name_is_tpu", "udp_geec_txns_on_chain",
                          "device_share_above_0.95"})


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def final_line(device: dict) -> str:
    """The contract's last line, nothing more in it."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


class Checks:
    """A phase's checks: the first failure raises, except that under
    ``--rehearse`` the device-path ones are recorded and carried in the
    phase's summary (which can then never say it passed)."""

    def __init__(self, phase: str, rehearse: bool):
        self.phase = phase
        self.rehearse = rehearse
        self.off_chip: list[str] = []

    def __call__(self, name: str, cond: bool, detail=None) -> None:
        emit({"phase": self.phase, "check": name, "holds": bool(cond),
              "detail": detail})
        if cond:
            return
        if self.rehearse and name in ONLY_ON_CHIP:
            self.off_chip.append(name)
            return
        raise AssertionError(f"{self.phase}: {name} failed: {detail}")


# ---------------------------------------------------------------------------
# seeded rows and their reference (children only; numpy + the native lib)
# ---------------------------------------------------------------------------

def seeded_rows(seed: int, n: int):
    """``n`` distinct seeded signature rows, one in ``INVALID_EVERY``
    invalid: s out of range, bad v, flipped hash byte, r not on the
    curve, in turn.  Returns ``sigs [n,65] u8, hashes [n,32] u8``."""
    import random

    import numpy as np

    from eges_tpu.crypto import native
    from eges_tpu.crypto.secp256k1 import N, P

    rng = random.Random(seed)
    sigs = np.zeros((n, 65), np.uint8)
    hashes = np.zeros((n, 32), np.uint8)
    for i in range(n):
        msg = rng.randbytes(32)
        priv = bytes([rng.randrange(1, 0x7F)]) + rng.randbytes(31)
        sig = bytearray(native.ec_sign(msg, priv))
        if i % INVALID_EVERY == INVALID_EVERY - 1:
            kind = (i // INVALID_EVERY) % 4
            if kind == 0:
                sig[32:64] = (N + 1 + rng.randrange(1 << 64)).to_bytes(
                    32, "big")
            elif kind == 1:
                sig[64] = 5
            elif kind == 2:
                msg = bytes([msg[0] ^ 0x40]) + msg[1:]
            else:
                while True:
                    x = rng.randrange(1, N)
                    if pow((x * x * x + 7) % P, (P - 1) // 2, P) != 1:
                        break
                sig[0:32] = x.to_bytes(32, "big")
        sigs[i] = np.frombuffer(bytes(sig), np.uint8)
        hashes[i] = np.frombuffer(msg, np.uint8)
    return sigs, hashes


def native_reference(sigs, hashes):
    """``(addrs [n,20] u8, ok [n] bool)`` from the native C++ batch
    recover — independent of every line of the device path."""
    import numpy as np

    from eges_tpu.crypto import native

    n = sigs.shape[0]
    pubs, okb = native.ec_recover_batch(hashes.tobytes(), sigs.tobytes(), n)
    addrs = np.zeros((n, 20), np.uint8)
    ok = np.frombuffer(okb, np.uint8).astype(bool)
    for i in np.flatnonzero(ok):
        addrs[i] = np.frombuffer(
            native.keccak256(pubs[64 * i:64 * i + 64])[12:], np.uint8)
    return addrs, ok


def same_rows(check: Checks, what: str, got, want) -> None:
    """Row-for-row equality of ``(addrs, ok)`` pairs — results, not
    timings."""
    import numpy as np

    (ga, gok), (wa, wok) = got, want
    bad_ok = np.flatnonzero(np.asarray(gok, bool) != wok)
    bad_addr = np.flatnonzero((np.asarray(ga) != wa).any(axis=1))
    check(what, bad_ok.size == 0 and bad_addr.size == 0,
          {"rows": int(wok.size), "valid": int(wok.sum()),
           "invalid": int((~wok).sum()),
           "ok_mismatch_rows": bad_ok[:8].tolist(),
           "addr_mismatch_rows": bad_addr[:8].tolist()})


def open_phase(phase: str, rehearse: bool):
    """What every chip-holding child does first: persistent cache on,
    jax's compile events heard, the device named and required to be a
    TPU.  Returns ``(check, events, devs, device)``."""
    import jax

    from eges_tpu.crypto import aotstore

    check = Checks(phase, rehearse)
    aotstore.enable_persistent_cache()
    events = CompileEvents()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit({"phase": phase, "device": device, "jax": jax.__version__,
          "cache_dir": aotstore.cache_dir(), "aot_dir": aotstore.aot_dir()})
    check("platform_is_tpu", device["platform"] == "tpu", device)
    return check, events, devs, device


def no_diverts(check: Checks, stats: dict) -> None:
    """A scheduler that diverted a window to the host, or tripped its
    breaker, did not run the path this smoke is about."""
    for key in ("device_errors", "breaker_trips", "breaker_diverted",
                "straggler_diverts"):
        check(f"scheduler_{key}_zero", stats[key] == 0, stats[key])


class CompileEvents:
    """jax's own monitoring events, per step: persistent-cache hits and
    misses, and the seconds the backend compile took — those of the
    thread that made this object (jax calls a listener on the compiling
    thread), so a compile running beside it is not counted in."""

    def __init__(self):
        import threading

        import jax.monitoring as mon

        self._thread = threading.get_ident()
        self.hits = self.misses = 0
        self.backend_compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _mine(self) -> bool:
        import threading

        return threading.get_ident() == self._thread

    def _event(self, name, **kw):
        if not self._mine():
            return
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **kw):
        if self._mine() and \
                name == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs

    def take(self) -> dict:
        out = {"persistent_cache_hits": self.hits,
               "persistent_cache_misses": self.misses,
               "compile_s": round(self.backend_compile_s, 3)}
        self.hits = self.misses = 0
        self.backend_compile_s = 0.0
        return out


def _warm_report(phase: str, info: dict, events: CompileEvents,
                 devs) -> None:
    """Per bucket: seconds to lower, to compile, to the first result,
    and where the executable came from."""
    ev = events.take()
    for w in info["warmed"]:
        source = ("artifact_store" if w["mode"] == "load" else
                  "persistent_cache" if ev["persistent_cache_hits"]
                  and not ev["persistent_cache_misses"] else "fresh_compile")
        stats = devs[0].memory_stats() or {}
        emit({"phase": phase, "bucket": w["bucket"], "op": w["op"],
              "lower_s": w["lower_s"], "compile_s": ev["compile_s"],
              "first_result_s": w["first_result_s"], "source": source,
              "persistent_cache": ev,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use")})


# ---------------------------------------------------------------------------
# phase verifier (a child: the only process on the chip while it lives)
# ---------------------------------------------------------------------------

def phase_verifier(seed: int, size: dict, rehearse: bool) -> dict:
    from eges_tpu.crypto import aotstore
    from eges_tpu.crypto.scheduler import scheduler_for
    from eges_tpu.crypto.verifier import default_verifier
    from eges_tpu.ops import pallas_kernels as pk
    from eges_tpu.utils.metrics import DEFAULT as metrics

    check, events, devs, device = open_phase("verifier", rehearse)
    check("ladder_kernels_enabled", pk.ladder_kernels_enabled(),
          {"EGES_TPU_PALLAS": os.environ.get("EGES_TPU_PALLAS", "")})
    check("interpret_off", not pk._default_interpret())

    raw = default_verifier()
    sched = scheduler_for(raw)
    store = aotstore.default_store()
    n_win = size["window_rows"]
    sigs, hashes = seeded_rows(seed, n_win + size["batch_rows"])
    want = native_reference(sigs, hashes)
    # the plain graph path compiles for this process's CPU backend on a
    # thread of its own while the kernel path traces and runs below
    graph_path = GraphPathOnCpu(sigs[:16], hashes[:16])

    # -- 1024-row windows through the scheduler the node builds --------
    bucket = min(n_win, sched.max_batch)
    info = raw.aot_prewarm(buckets=(bucket,), store=store)
    _warm_report("verifier", info, events, devs)
    # one columnar window submit: the rows enter in one lock
    # acquisition, so the scheduler cuts them into full max_batch windows
    got = _rows_of(sched.recover_window(hashes[:n_win], sigs[:n_win]))
    same_rows(check, "windows_equal_native_row_for_row", got,
              (want[0][:n_win], want[1][:n_win]))
    flights = sched.flights()
    emit({"phase": "verifier", "window_buckets": _count(
        f["bucket"] for f in flights),
        "window_rows": _count(f["rows"] for f in flights)})
    no_diverts(check, sched.stats())

    # the program that ran holds the Mosaic kernels: the module the
    # artifact store serialized for this bucket IS what the lane called
    payload = store.load("recover", raw._pad(bucket), raw.device_kind)
    check("artifact_saved_for_bucket", payload is not None)
    from jax import export as jax_export
    mlir = jax_export.deserialize(payload).mlir_module()
    check("tpu_custom_call_in_program", "tpu_custom_call" in mlir,
          {"count": mlir.count("tpu_custom_call")})

    # -- one 16384-row batch straight through BatchVerifier.ecrecover --
    bs, bh = sigs[n_win:], hashes[n_win:]
    info = raw.aot_prewarm(buckets=(bs.shape[0],), store=store)
    _warm_report("verifier", info, events, devs)
    addrs, _pubs, ok = raw.ecrecover(bs, bh)
    same_rows(check, "batch_equal_native_row_for_row", (addrs, ok),
              (want[0][n_win:], want[1][n_win:]))

    # -- kernel path against graph path, bit for bit -------------------
    if pk.ladder_kernels_enabled():
        _kernels_against_graph(check, seed)
    else:  # a rehearsal: interpret-mode loop kernels compile for hours
        emit({"phase": "verifier", "skipped": "kernels_against_graph"})
    same_rows(check, "kernel_path_equals_graph_path_bit_for_bit",
              (got[0][:16], got[1][:16]), graph_path.result())

    for name in ("verifier.aot_load_errors",
                 "verifier.compile_cache_errors"):
        check(name.split(".")[1] + "_zero",
              metrics.counter(name).value == 0, metrics.counter(name).value)
    sched.close()
    return {"device": device, "off_chip": check.off_chip}


def _rows_of(recovered: list):
    """A scheduler window's per-row result list (a 20-byte address, or
    ``None`` for an invalid row) as ``(addrs [n,20] u8, ok [n] bool)``."""
    import numpy as np

    addrs = np.zeros((len(recovered), 20), np.uint8)
    ok = np.zeros(len(recovered), bool)
    for i, r in enumerate(recovered):
        if r is not None:
            addrs[i] = np.frombuffer(r, np.uint8)
            ok[i] = True
    return addrs, ok


def _count(values) -> dict:
    out: dict = {}
    for v in values:
        out[str(v)] = out.get(str(v), 0) + 1
    return dict(sorted(out.items(), key=lambda kv: int(kv[0])))


def _kernels_against_graph(check: Checks, seed: int) -> None:
    """The loop kernels in isolation against the graph ops they mirror
    (mod-N multiply, the two pow ladders) and the keccak kernel against
    the host golden — on whatever backend this process has: Mosaic on
    the chip, interpret mode in a rehearsal."""
    import random

    import jax.numpy as jnp
    import numpy as np

    from eges_tpu.crypto.keccak import keccak256
    from eges_tpu.ops import pallas_kernels as pk
    from eges_tpu.ops.bigint import FN, FP, N, P, int_to_limbs
    from eges_tpu.ops.keccak_tpu import RATE

    rng = random.Random(seed + 1)
    n = 9

    def limbs(mod):
        return jnp.asarray(np.stack([int_to_limbs(rng.randrange(mod))
                                     for _ in range(n)]))

    ka, kb, fa = limbs(N), limbs(N), limbs(P)
    check("fn_mul_kernel_equals_graph", np.array_equal(
        np.asarray(pk.fn_mul_pallas(ka, kb)), np.asarray(FN.mul(ka, kb))))
    check("pow_mod_p_kernel_equals_graph", np.array_equal(
        np.asarray(FP.canon(pk.pow_mod_pallas(fa, P - 2, "p"))),
        np.asarray(FP.canon(FP.pow_const(fa, P - 2)))))
    check("pow_mod_n_kernel_equals_graph", np.array_equal(
        np.asarray(pk.pow_mod_pallas(ka, N - 2, "n")),
        np.asarray(FN.pow_const(ka, N - 2))))
    msgs = [bytes(range(64)), rng.randbytes(64), b"\xff" * 64]
    words = np.zeros((len(msgs), 34), np.uint32)
    for i, m in enumerate(msgs):
        buf = bytearray(RATE)
        buf[:len(m)] = m
        buf[len(m)] ^= 0x01
        buf[RATE - 1] ^= 0x80
        words[i] = np.frombuffer(bytes(buf), "<u4")
    dig = np.asarray(pk.keccak_block_pallas(jnp.asarray(words))) \
        .astype("<u4").view(np.uint8).reshape(len(msgs), 32)
    check("keccak_kernel_equals_host", all(
        bytes(dig[i]) == keccak256(m) for i, m in enumerate(msgs)))


class GraphPathOnCpu:
    """The first rows of the run recovered by the plain XLA graph — the
    path every tier-1 test runs — on this process's CPU backend, for the
    bit-for-bit comparison with what the kernel path returns from the
    chip.  The gate keys on the default backend, so the trace is made
    with the gate steered here, at once; the minute of XLA CPU compile
    then runs on a thread beside the kernel path's own tracing."""

    def __init__(self, sigs, hashes):
        import threading

        import jax

        from eges_tpu.crypto.verifier import ecrecover_batch
        from eges_tpu.ops import pallas_kernels as pk

        self._t0 = time.monotonic()
        cpu = jax.devices("cpu")[0]
        # committed to the CPU device: the lowering is then for it
        self._args = [jax.device_put(a, cpu) for a in (sigs, hashes)]
        gate = pk.ladder_kernels_enabled
        pk.ladder_kernels_enabled = lambda: False
        try:
            self._lowered = jax.jit(ecrecover_batch).lower(*self._args)
        finally:
            pk.ladder_kernels_enabled = gate
        self._out = self._err = None
        self._thread = threading.Thread(target=self._compile_and_run,
                                        name="graph-path-on-cpu")
        self._thread.start()

    def _compile_and_run(self):
        try:
            self._out = self._lowered.compile()(*self._args)
        except BaseException as e:  # re-raised by result()
            self._err = e

    def result(self):
        """``(addrs [n,20] u8, ok [n] bool)``."""
        import numpy as np

        self._thread.join()
        if self._err is not None:
            raise self._err
        addrs, _pubs, ok = self._out
        emit({"phase": "verifier", "graph_path_on_cpu_s":
              round(time.monotonic() - self._t0, 3),
              "rows": int(self._args[0].shape[0])})
        return np.asarray(addrs), np.asarray(ok).astype(bool)


# ---------------------------------------------------------------------------
# phase mesh (--chips 4; a child)
# ---------------------------------------------------------------------------

def phase_mesh(seed: int, size: dict, rehearse: bool) -> dict:
    import numpy as np

    from eges_tpu.crypto import aotstore
    from eges_tpu.crypto.scheduler import scheduler_for
    from eges_tpu.crypto.verifier import MeshBatchVerifier, default_verifier

    check, events, devs, device = open_phase("mesh", rehearse)
    check("four_devices", len(devs) == 4, len(devs))

    raw = default_verifier()
    check("default_verifier_is_mesh", isinstance(raw, MeshBatchVerifier),
          type(raw).__name__)
    kw = ({"max_batch": size["sched_max_batch"]}
          if size["sched_max_batch"] else {})
    sched = scheduler_for(raw, **kw)
    targets = raw.device_targets()
    check("one_lane_per_device", sched.stats()["lanes"] == len(devs) and
          [t.device for t in targets] == list(devs))

    n = size["mesh_rows"]
    sigs, hashes = seeded_rows(seed, n)
    want = native_reference(sigs, hashes)
    chunk = -(-sched.max_batch // len(targets))

    # the shared executable registry is filled once, from zeros on the
    # default device: every lane must still run on ITS device
    info = raw.aot_prewarm(buckets=(chunk,),
                           store=aotstore.default_store())
    _warm_report("mesh", info, events, devs)
    for t in targets:
        st = t.commit_recover(t.stage_recover(sigs[:chunk], hashes[:chunk]))
        held = {d for arr in st.out for d in arr.devices()}
        check(f"lane_{t.index}_ran_on_its_device", held == {t.device},
              {"lane_device": str(t.device),
               "result_on": sorted(str(d) for d in held)})
        same_rows(check, f"lane_{t.index}_equals_native",
                  t.collect_recover(st),
                  (want[0][:chunk], want[1][:chunk]))

    # the rows through the scheduler's lanes (one columnar submit:
    # full windows, each split across the lanes)
    got = _rows_of(sched.recover_window(hashes, sigs))
    same_rows(check, "lanes_equal_native_row_for_row", got, want)
    st = sched.stats()
    per_lane = [{"device": d["device"], "rows": d["rows"],
                 "batches": d["batches"]} for d in st["devices"]]
    emit({"phase": "mesh", "lanes": per_lane,
          "window_splits": st["window_splits"]})
    check("every_lane_held_rows", all(d["rows"] > 0 for d in per_lane),
          per_lane)
    no_diverts(check, st)

    # device 0 alone, the same rows chunk by chunk
    alone = [targets[0].recover_addresses(sigs[i:i + chunk],
                                          hashes[i:i + chunk])
             for i in range(0, n, chunk)]
    alone = (np.concatenate([a for a, _ in alone]),
             np.concatenate([o for _, o in alone]))
    same_rows(check, "lanes_equal_device0_alone", got, alone)

    # the full-mesh sharded recover and its on-device tally (psum)
    check("collective_is_psum", raw.collective_for(n) == "psum",
          raw.collective_for(n))
    t0 = time.monotonic()
    addrs, _pubs, ok, tally = raw._sharded(*raw._to_device(sigs, hashes))
    emit({"phase": "mesh", "sharded_first_result_s":
          round(time.monotonic() - t0, 3), **events.take()})
    shard_devs = {d for d in ok.devices()}
    check("sharded_rows_on_every_device", shard_devs == set(devs),
          sorted(str(d) for d in shard_devs))
    check("tally_equals_valid_rows", int(tally) == int(want[1].sum()),
          {"tally": int(tally), "valid": int(want[1].sum())})
    same_rows(check, "full_mesh_equals_native",
              (np.asarray(addrs), np.asarray(ok).astype(bool)), want)
    fa, _fp, fok = raw.ecrecover(sigs, hashes)
    same_rows(check, "full_mesh_facade_equals_device0_alone", (fa, fok),
              alone)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    emit({"phase": "mesh", "peak_bytes_in_use": peaks})
    sched.close()
    return {"device": device, "off_chip": check.off_chip}


# ---------------------------------------------------------------------------
# phase served (this parent drives it; the nodes are the children)
# ---------------------------------------------------------------------------

def seeded_transfers(seed: int, accounts: int, per_account: int):
    """Signed transfers from seeded accounts, nonce-round-major (every
    account's nonce j before any account's nonce j+1).  Returns
    ``(txns, sender address per account)``."""
    import random

    from eges_tpu.core.types import Transaction
    from eges_tpu.crypto import secp256k1 as secp

    rng = random.Random(seed + 2)
    privs = [bytes([rng.randrange(1, 0x7F)]) + rng.randbytes(31)
             for _ in range(accounts)]
    senders = [secp.pubkey_to_address(secp.privkey_to_pubkey(p))
               for p in privs]
    txns = []
    for nonce in range(per_account):
        for a, priv in enumerate(privs):
            txns.append(Transaction(
                nonce=nonce, gas_price=0, gas_limit=21_000,
                to=senders[(a + 1) % accounts], value=0).signed(priv))
    return txns, senders


def phase_served(seed: int, size: dict, rehearse: bool) -> dict:
    import socket
    import tempfile

    from harness import cluster

    check = Checks("served", rehearse)
    n_nodes, chip_node, other = 3, 0, 1
    chip_port = cluster.RPC_BASE + chip_node
    other_port = cluster.RPC_BASE + other

    def rpc(method, params, port):
        return cluster._rpc(method, params, port, timeout=60, tries=5)

    def rpc_batch(calls, port, timeout=120):
        import urllib.request
        body = json.dumps([{"jsonrpc": "2.0", "id": i, "method": m,
                            "params": p}
                           for i, (m, p) in enumerate(calls)]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}", data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=timeout).read())
        out.sort(key=lambda r: r["id"])
        bad = [r for r in out if "error" in r]
        if bad:
            raise RuntimeError(f"rpc batch errors: {bad[:3]}")
        return [r["result"] for r in out]

    txns, senders = seeded_transfers(seed, size["accounts"],
                                     size["txns_per_account"])
    dirpath = tempfile.mkdtemp(prefix="chip_smoke_cluster_")
    t0 = time.monotonic()
    pids: list[int] = []
    try:
        # upstream's documented cluster (BASELINE.md / config-test.json)
        cluster.start_cluster_jax_first(
            dirpath, n_nodes, chip_node, txn_per_block=1000, txn_size=100,
            warm_deadline_s=size["warm_deadline_s"])
        setup_s = round(time.monotonic() - t0, 3)
        pids = [p for p in cluster.load_meta(dirpath)["pids"] if p]
        for port in (chip_port, other_port, cluster.RPC_BASE + 2):
            check(f"rpc_{port}_answers",
                  cluster._wait_for_rpc(port, 120.0))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            heights = [int(rpc("eth_blockNumber", [],
                               cluster.RPC_BASE + i), 16)
                       for i in range(n_nodes)]
            if min(heights) >= 1:
                break
            time.sleep(1)
        check("chain_is_live_on_every_node", min(heights) >= 1, heights)
        met = rpc("thw_metrics", [], chip_port)
        emit({"phase": "served", "setup_s": setup_s,
              "chip_node_cold_start_s":
                  met.get("verifier.cold_start_seconds"),
              "aot_loads": _metric(met, "verifier.aot_loads"),
              "aot_compiles": _metric(met, "verifier.aot_compiles"),
              "txns": len(txns), "accounts": len(senders)})

        # traffic, back to back so that signed transfers and not idle
        # blocks' vote checks make up the verifier's rows (a one-row
        # window is the scheduler's to divert to the host, by design).
        # RPC batches alternate between nodes 1 and 2, whose admissions
        # reach the chip node by gossip as whole windows; the last nonce
        # round enters by the chip node's own eth_sendRawTransaction.
        # Nonce round after nonce round: the pools hold a sender's later
        # nonces until the earlier ones arrive.  UDP Geec transactions
        # go to the chip node as loadtest sends them.
        udp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp.settimeout(1.0)
        before = rpc("thw_metrics", [], chip_port)
        t_traffic = time.monotonic()
        hashes = []
        step = size["rpc_batch"]
        last_round = len(txns) - len(senders)
        cuts = list(range(0, last_round, step)) + [last_round, len(txns)]
        for k, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
            part = txns[lo:hi]
            port = (chip_port if lo == last_round
                    else cluster.RPC_BASE + 1 + k % 2)
            sent = rpc_batch([("eth_sendRawTransaction",
                               ["0x" + t.encode().hex()]) for t in part],
                             port)
            check(f"batch_{k}_accepted_by_port_{port}",
                  sent == ["0x" + t.hash.hex() for t in part])
            hashes += sent
            for i in range(lo * size["n_udp"] // len(txns),
                           (lo + len(part)) * size["n_udp"] // len(txns)):
                udp.sendto(b"smoke payload %d" % i,
                           ("127.0.0.1", cluster.TXN_BASE + chip_node))
                time.sleep(0.005)  # loadtest's pacing: UDP has no other
        udp.close()
        # progress by the senders' nonces (a state read); a receipt
        # lookup decodes its whole block, so receipts are read once, after
        want_nonce = hex(size["txns_per_account"])
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            nonces = rpc_batch([("eth_getTransactionCount",
                                 ["0x" + a.hex(), "latest"])
                                for a in senders], other_port)
            if all(x == want_nonce for x in nonces):
                break
            time.sleep(1)
        # the chip node's counters as the traffic ends: what follows
        # (slow reads from the other nodes, the UDP drain) is idle time
        # whose blocks add nothing but one-row vote checks
        met = rpc("thw_metrics", [], chip_port)
        emit({"phase": "served", "traffic_s":
              round(time.monotonic() - t_traffic, 3),
              "chip_node_rows_during_traffic": {
                  k: _metric(met, "verifier." + k)
                  - _metric(before, "verifier." + k)
                  for k in ("rows", "host_rows", "native_rows")}})
        # the chip node proposes what it was sent once it wins a block
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and rpc(
                "thw_pendingGeecTxns", [], chip_port):
            time.sleep(1)
        time.sleep(3)  # its last block reaches the other nodes

        # every signed transaction committed, with the expected sender
        # — read from a node OTHER than the chip node.  Each full block
        # lists what it committed; the RPC carries no "from", so the
        # sender the chain recovered is the account whose nonce the
        # transaction consumed; a receipt lookup decodes its whole
        # block, so status 1 is read for a seeded sample of them.
        tops = [int(rpc("eth_blockNumber", [], cluster.RPC_BASE + i), 16)
                for i in range(n_nodes)]
        heights = [hex(h) for h in range(1, tops[other] + 1)]
        blocks = [b for lo in range(0, len(heights), 64) for b in rpc_batch(
            [("eth_getBlockByNumber", [h, False])
             for h in heights[lo:lo + 64]], other_port)]
        mined = {h for b in blocks for h in b["transactions"]}
        check("every_txn_in_a_committed_block_on_node_%d" % other,
              mined.issuperset(hashes),
              {"txns": len(hashes),
               "missing": len(set(hashes) - mined)})
        check("every_sender_nonce_consumed_on_node_%d" % other,
              all(x == want_nonce for x in nonces),
              {"want": size["txns_per_account"],
               "got": sorted({int(x, 16) for x in nonces})})
        import random
        sample = random.Random(seed).sample(hashes, min(512, len(hashes)))
        recs = rpc_batch([("eth_getTransactionReceipt", [h])
                          for h in sample], other_port)
        check("sampled_receipts_have_status_1_on_node_%d" % other,
              all(r is not None and r["status"] == "0x1" for r in recs),
              {"sampled": len(recs)})

        # identical block hash at every height all three have committed
        top = min(tops)
        chains = [[b["hash"] for b in blocks[:top]]] + [
            [b["hash"] for b in rpc_batch(
                [("eth_getBlockByNumber", [h, False])
                 for h in heights[:top]], cluster.RPC_BASE + i)]
            for i in range(n_nodes) if i != other]
        check("block_hashes_identical_on_all_nodes",
              chains[0] == chains[1] == chains[2], {"heights": top})
        geec = sum(b["geecTxnCount"] for b in blocks)
        # the chip node: which device, how much of the work, no diverts
        name = str(met.get("verifier.device_name", ""))
        check("device_name_is_tpu", "tpu" in name.lower(), name)
        share = met.get("verifier.device_share")
        check("device_share_above_0.95", (share or 0) > 0.95,
              {"device_share": share,
               "device_rows": _metric(met, "verifier.rows"),
               "native_rows": _metric(met, "verifier.native_rows"),
               "host_rows": _metric(met, "verifier.host_rows")})
        sst = met["scheduler"]
        no_diverts(check, sst)
        for name in ("verifier.aot_load_errors",
                     "verifier.compile_cache_errors"):
            check(name.split(".")[1] + "_zero", _metric(met, name) == 0,
                  _metric(met, name))
        buckets = {k.split("bucket=")[1]: _metric(met, k)
                   for k in met if k.startswith(
                       "verifier.device_seconds;bucket=")}
        emit({"phase": "served", "window_buckets": dict(sorted(
            buckets.items(), key=lambda kv: int(kv[0]))),
            "largest_bucket": max((int(b) for b in buckets), default=0),
            "scheduler": {k: sst[k] for k in (
                "batches", "rows", "bucket_rows", "host_diverted",
                "cache_hits", "window_submits", "window_rows")},
            "height": top, "geec_on_chain": geec})
        check("udp_geec_txns_on_chain", geec >= int(size["n_udp"] * 0.8),
              {"on_chain": geec, "sent": size["n_udp"]})
    except BaseException:
        for i in range(n_nodes):  # what the nodes said, for the post-mortem
            tail = cluster.Runner().read_log(
                os.path.join(dirpath, f"node{i}.log"))[-3000:]
            print(f"--- node{i}.log (tail)\n"
                  + tail.decode(errors="replace"), file=sys.stderr)
        raise
    finally:
        meta = cluster.load_meta(dirpath)
        pids = pids or [p for p in (meta or {}).get("pids", []) if p]
        cluster.kill_cluster(dirpath)
        _reap(pids)
    return {"off_chip": check.off_chip}


def _metric(met: dict, key: str):
    v = met.get(key, 0)
    return v.get("count", 0) if isinstance(v, dict) else v


def _reap(pids, grace_s: float = 20.0) -> None:
    """Every process this script started is gone before it returns."""
    import signal

    deadline = time.monotonic() + grace_s
    for pid in pids:
        while True:
            try:
                done, _ = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                break
            if done:
                break
            if time.monotonic() > deadline:
                os.kill(pid, signal.SIGKILL)
                os.waitpid(pid, 0)
                break
            time.sleep(0.2)


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------

PHASES = {"verifier": phase_verifier, "mesh": phase_mesh,
          "served": phase_served}


def run_child(phase: str, args, size: dict) -> dict:
    """Run one phase as a child process — the only holder of the chip
    while it lives — echoing its JSON lines; returns its summary."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ, PYTHONPATH=HERE)
    if phase == "mesh" and args.rehearse:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
    import threading

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env, cwd=HERE,
                            text=True)
    watchdog = threading.Timer(size["child_timeout_s"], proc.kill)
    watchdog.start()
    summary = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith('{"summary"'):
                summary = json.loads(line)["summary"]
            elif line:
                print(line, flush=True)
    finally:
        rc = proc.wait()
        watchdog.cancel()
    if rc != 0 or summary is None:
        raise RuntimeError(f"phase {phase} failed (rc={rc})")
    emit({"phase": phase, "passed": not summary["off_chip"],
          "off_chip": summary["off_chip"]})
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: phase mesh and what it is compared with, "
                         "and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX has; "
                         "device-path checks are recorded, not raised; "
                         "never exits 0")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    size = TINY if args.rehearse else FULL

    if not os.path.isdir(os.path.join(HERE, "eges_tpu")):
        print("chip_smoke.py runs from the root of an eges-tpu checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    if args.phase:  # one phase alone; as a child it may touch JAX
        summary = PHASES[args.phase](args.seed, size, args.rehearse)
        emit({"summary": summary})
        return 0

    t0 = time.monotonic()
    from eges_tpu.crypto import native

    lib = native.ensure_built()  # raises if make fails
    emit({"phase": "build", "native_lib": os.path.relpath(lib, HERE),
          "build_s": round(time.monotonic() - t0, 3)})

    off_chip: list[str] = []
    if args.chips == 4:
        summary = run_child("mesh", args, size)
        off_chip += summary["off_chip"]
    else:
        summary = run_child("verifier", args, size)
        off_chip += summary["off_chip"]
        off_chip += phase_served(args.seed, size, args.rehearse)["off_chip"]
        emit({"phase": "served", "passed": not off_chip})
    device = summary["device"]
    emit({"phase": "all", "wall_s": round(time.monotonic() - t0, 3),
          "jax_in_parent": "jax" in sys.modules})
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported jax")
    if off_chip or device["platform"] != "tpu" \
            or device["count"] != args.chips:
        emit({"passed": False, "off_chip": off_chip, "device": device})
        return 1
    print(final_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
