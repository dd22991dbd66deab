"""Multi-host (DCN) device mesh: the distributed communication backend.

SURVEY §2.3 maps the reference's host plane — NCCL/MPI-style fan-out of
batches across machines (ref: eth/handler.go:1058-1103 per-peer send
loops; the Geec deployment scatters verify work the same way) — onto
``jax.distributed``: every host runs one process, the processes
rendezvous at a coordinator, and their local chips form ONE global
:class:`jax.sharding.Mesh`.  Collectives over the mesh axis then ride
ICI within a host and DCN between hosts, inserted by XLA from the same
``shard_map`` program that drives the single-host path — no second code
path for "networked" mode, which is the whole point of the design.

Two layers:

* :func:`initialize` / :func:`global_mesh` — library surface a real
  multi-host deployment calls once at startup (mirrors
  ``jax.distributed.initialize``; the node CLI exposes it via
  ``--coordinator/--processId/--numProcesses``).
* :func:`dryrun_multihost` — the CI proof: spawns N real OS processes
  on this machine (CPU backend, a few virtual devices each), forms the
  global mesh across them, runs the sharded batch verifier with its
  cross-process ``psum`` tally, and checks every process sees the same
  correct global count.  This exercises the actual multi-process
  runtime (coordination service, cross-host collectives), not a
  single-process simulation of it.
"""

from __future__ import annotations

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def initialize(coordinator: str, num_processes: int, process_id: int,
               platform: str = "") -> None:
    """Join the distributed runtime (call before any other jax use).

    ``coordinator`` is ``host:port`` of process 0 — the DCN rendezvous
    point.  On CPU backends the cross-process collective transport is
    gloo (the only one the wheel ships); TPU backends use the native
    ICI/DCN stack and ignore it.
    """
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if (platform or "cpu") == "cpu":
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # analysis: allow-swallow(older jax: single implementation, no knob)
        except Exception:
            pass
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    # LOCALHOST rigs only (the dryrun launcher sets the env): a real
    # multi-machine CPU deployment must NOT pin gloo to loopback, or
    # every cross-host connect dials the wrong machine.
    if os.environ.get("EGES_TPU_GLOO_LOOPBACK") == "1":
        _pin_gloo_loopback()


def _pin_gloo_loopback() -> None:
    """Re-register the CPU backend factory with gloo collectives pinned
    to the loopback interface.

    jax builds gloo with ``hostname=None, interface=None`` and gloo then
    binds a NIC from its own discovery; inside this sandboxed host that
    picked an interface whose worker-to-worker connects time out
    ("Gloo context initialization failed: Connect timeout") even though
    the hostname resolves to 127.0.0.1.  The dry run is strictly
    localhost, so pin both ends to loopback.  Harmless on real
    multi-host TPO deployments: those use the native ICI/DCN stack, not
    the CPU gloo transport.

    Uses jax PRIVATE internals (jax._src.{distributed,xla_bridge},
    xla_client._xla.make_gloo_tcp_collectives) — written against the
    baked-in jax 0.5.x; a jax upgrade may rename any of them.  That
    must degrade to the default gloo factory with a readable log line,
    not an opaque dryrun crash (r4 advisor finding)."""
    try:
        from jax._src import distributed, xla_bridge
        from jaxlib import xla_client

        def make(*_a, **_kw):
            collectives = xla_client._xla.make_gloo_tcp_collectives(
                distributed_client=distributed.global_state.client,
                hostname="127.0.0.1")
            return xla_bridge.make_cpu_client(collectives=collectives)

        # same flags as jax's own cpu registration; the factory table is
        # keyed by name, so this simply replaces the default factory (it
        # must run before the first backend use or jax raises)
        xla_bridge.register_backend_factory("cpu", make, priority=0,
                                            fail_quietly=False)
    except Exception as exc:  # AttributeError/ImportError on jax bump
        print(f"multihost: gloo loopback pin unavailable on this jax "
              f"version ({exc!r}); using the default gloo factory — "
              f"cross-process connects may pick a non-loopback NIC",
              file=sys.stderr, flush=True)


def global_mesh(axis: str = "dp"):
    """One mesh over every device of every process, in id order."""
    import numpy as np
    import jax

    return jax.sharding.Mesh(np.array(jax.devices()), (axis,))


def make_global_rows(mesh, axis: str, *arrays):
    """Lift host-resident global batches into row-sharded global
    ``jax.Array``s.  Every process passes the SAME full batch (consensus
    batches are deterministic — each host derived them from the same
    block); the callback hands each local device only its row slice, so
    nothing materializes twice."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for a in arrays:
        spec = P(axis, *([None] * (a.ndim - 1)))
        sharding = NamedSharding(mesh, spec)
        out.append(jax.make_array_from_callback(
            a.shape, sharding, lambda idx, a=a: a[idx]))
    return tuple(out)


def _worker_body(process_id: int, num_processes: int,
                 coordinator: str) -> None:
    """One process of the dry run: join, mesh, verify, tally, check."""
    initialize(coordinator, num_processes, process_id, platform="cpu")

    import numpy as np
    import jax

    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.verifier import make_sharded_ecrecover

    mesh = global_mesh("dp")
    n_devices = mesh.shape["dp"]
    rows = 2 * n_devices

    sigs = np.zeros((rows, 65), np.uint8)
    hashes = np.zeros((rows, 32), np.uint8)
    privs = []
    for i in range(rows):
        msg = bytes([(i % 255) + 1]) * 32
        priv = bytes([(i % 200) + 5]) * 32
        privs.append(priv)
        sigs[i] = np.frombuffer(host.ecdsa_sign(msg, priv), np.uint8)
        hashes[i] = np.frombuffer(msg, np.uint8)

    gsigs, ghashes = make_global_rows(mesh, "dp", sigs, hashes)
    fn = make_sharded_ecrecover(mesh, "dp")
    # Compile ahead-of-time, then meet at a COORDINATION-SERVICE
    # barrier (not a collective) before the first execution.  The gloo
    # transport rendezvouses lazily at the first collective with ~30 s
    # timeouts; on a 1-core host one worker can hit the persistent
    # compile cache while the other compiles from scratch, and that
    # skew alone blew the rendezvous ("Gloo context initialization
    # failed: Connect timeout / GetKeyValue() timed out").
    compiled = fn.lower(gsigs, ghashes).compile()  # fn is jitted already
    from jax._src import distributed as _dist
    _dist.global_state.client.wait_at_barrier("eges_compiled",
                                              timeout_in_ms=900_000)
    addrs, _pubs, ok, tally = compiled(gsigs, ghashes)

    # the psum tally is replicated: every process holds the global count
    assert int(tally) == rows, f"pid {process_id}: tally {int(tally)} != {rows}"
    # outputs are globally sharded; each process checks the rows it owns
    checked = 0
    # slice objects are unhashable before py3.12 — key by their bounds
    ok_shards = {(s.index[0].start, s.index[0].stop): np.asarray(s.data)
                 for s in ok.addressable_shards}
    for shard in addrs.addressable_shards:
        rs = shard.index[0]
        data = np.asarray(shard.data)
        assert ok_shards[(rs.start, rs.stop)].all(), (
            f"pid {process_id}: rejected valid rows")
        for j, i in enumerate(range(*rs.indices(rows))):
            want = host.pubkey_to_address(host.privkey_to_pubkey(privs[i]))
            assert bytes(data[j]) == want, (
                f"pid {process_id}: row {i} address mismatch")
            checked += 1
    print(f"dryrun_multihost OK pid={process_id}/{num_processes} "
          f"devices={n_devices} tally={int(tally)} local_rows={checked}",
          flush=True)


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.settimeout(1.0)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def dryrun_multihost(num_processes: int = 2, devices_per_proc: int = 4,
                     timeout: float = 1800.0) -> None:
    """Prove the DCN path: ``num_processes`` OS processes, one global
    mesh, sharded verify + cross-process psum, every process asserting
    the global tally.  CPU backend; the same program shape runs
    unchanged on real multi-host TPU (ICI inside a host, DCN between).
    """
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the workers never touch a chip
    # the dryrun is strictly localhost: have the workers rebuild their
    # gloo collectives pinned to loopback (see _pin_gloo_loopback)
    env["EGES_TPU_GLOO_LOOPBACK"] = "1"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={devices_per_proc}"]
    ).strip()
    env["PYTHONPATH"] = _REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    from eges_tpu.crypto.aotstore import cache_dir
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()

    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "eges_tpu.parallel.multihost",
             "--worker", str(pid), str(num_processes), coordinator],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for pid in range(num_processes)
    ]
    outs = []
    failed = False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, _ = p.communicate()
            failed = True
        outs.append(out or "")
        failed = failed or p.returncode != 0
    for pid, out in enumerate(outs):
        sys.stdout.write(out)
        if f"dryrun_multihost OK pid={pid}" not in out:
            failed = True
    if failed:
        raise RuntimeError(
            "dryrun_multihost failed; worker output above (last worker: "
            f"{outs[-1][-500:]!r})")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        _worker_body(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
        sys.exit(0)
    dryrun_multihost(int(sys.argv[1]) if len(sys.argv) > 1 else 2,
                     int(sys.argv[2]) if len(sys.argv) > 2 else 4)
