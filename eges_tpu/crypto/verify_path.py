"""What stands between a process's signature rows and their answers,
built in ONE place: the node service (``node/service.py``), the verify
sidecar's entry point (``crypto/sidecar.py``) and the benchmark's drivers
all call :func:`build`, and whoever holds a device warms it by
:func:`warm`, so the sidecar serves exactly what a node on the chip would
have built for itself.

The four modes of ``--verifier``:

* ``jax``: the device facade (``default_verifier()``: one chip, or a
  mesh over the chips this process sees) behind the coalescing scheduler
  (``scheduler_for``), compiled graphs shared through the artifact store;
* ``native``: the host C++ batch verifier behind the same scheduler; no
  jax in the process;
* ``sidecar``: a :class:`~eges_tpu.crypto.sidecar.SidecarClient` on a
  local socket, in the scheduler's place; the scheduler, the recovery
  cache and the chip are the sidecar process's, shared with every other
  node of the host; no jax in the process;
* ``none``: no verifier; callers keep the per-entry host path.

This module must stay importable WITHOUT JAX (it imports jax only inside
the ``jax`` branch).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from eges_tpu.utils import heap


@dataclass
class VerifyPath:
    """``verifier`` is what the chain, the consensus node and the pool
    hold (a scheduler, a sidecar client, or None); ``raw`` is the device
    facade behind a scheduler (None where this process has none);
    ``platform`` names the jax backend of a ``jax`` path."""

    mode: str
    raw: object | None = None
    verifier: object | None = None
    platform: str | None = None


def _quiet(kind: str, **kw) -> None:
    """Where the caller gave no log."""


def build(mode: str, *, sidecar_path: str = "", log=_quiet,
          **scheduler_kwargs) -> VerifyPath:
    """The verify path of ``mode``.  ``log(kind, **fields)`` takes the
    lines a service writes; ``scheduler_kwargs`` reach ``scheduler_for``
    (a rehearsal's small ``max_batch``)."""
    if mode == "sidecar":
        if not sidecar_path:
            raise ValueError("--verifier sidecar needs --sidecar PATH")
        from eges_tpu.crypto.sidecar import SidecarClient
        client = SidecarClient(sidecar_path)
        log("verify sidecar", path=sidecar_path,
            connected=client.stats()["connected"])
        # this process has no scheduler whose dispatcher would: the
        # client in hand is where its verify path first serves
        heap.settle()
        return VerifyPath(mode, verifier=client)
    raw, platform = None, None
    if mode == "jax":
        # share compiled verifier graphs across node processes and
        # restarts (the recover graph is the expensive compile); a
        # broken cache logs + counts verifier.compile_cache_errors
        # and the process runs uncached
        from eges_tpu.crypto.aotstore import enable_persistent_cache
        enable_persistent_cache()
        # default_verifier refuses a platform nobody asked for: a
        # process that wanted the chip never verifies on the CPU
        # backend in silence
        from eges_tpu.crypto.verifier import default_verifier
        raw = default_verifier()
        platform = raw.device_kind.partition(":")[0]
        log("verifier device", device=raw.device_kind)
    elif mode == "native":
        from eges_tpu.crypto.verify_host import NativeBatchVerifier
        raw = NativeBatchVerifier()
    elif mode != "none":
        raise ValueError(f"no verifier mode {mode!r}")
    return on_scheduler(VerifyPath(mode, raw=raw, platform=platform),
                        log=log, **scheduler_kwargs)


def on_scheduler(path: VerifyPath, *, log=_quiet,
                 **scheduler_kwargs) -> VerifyPath:
    """``path.raw`` behind its scheduler.  The coalescing scheduler +
    sender-recovery cache fronts the device for every consumer (chain
    body validation, the consensus node's vote paths, the txpool flush,
    a sidecar's clients): concurrent submissions merge into one device
    batch per micro-window, and commit-time re-verification of gossiped
    signatures becomes a cache hit."""
    if path.raw is None:
        return path
    from eges_tpu.crypto.scheduler import scheduler_for
    path.verifier = scheduler_for(path.raw, **scheduler_kwargs)
    # a mesh verifier (default_verifier over >1 visible device) turns
    # the scheduler into the mesh dispatcher: one window lane per
    # device.  Surface the topology in the log so an operator can see
    # the fan-out without scraping stats.
    lanes = path.verifier.stats()["lanes"]
    if lanes > 1:
        log("verifier mesh dispatch enabled", devices=lanes)
    return path


def warm(path: VerifyPath, *, log=_quiet) -> dict | None:
    """Warm the recover graphs of a ``jax`` path NOW; None for a path
    that has nothing to compile.  A cold bucket costs about two minutes
    of Python tracing plus a quarter of a minute of compiling on the
    kernel path, and letting that happen lazily inside a consensus
    message handler wedges the event loop mid-election (diagnosed via
    the SIGUSR1 dump).  The warm goes through the AOT artifact store: a
    process restarted on a machine that compiled before deserializes
    the stored executable instead of re-tracing (and a first-ever
    compile leaves an artifact behind for the next process).  On the
    chip EVERY bucket the scheduler can pad a window to is warmed before
    the process serves; on the CPU backend (asked for by name: tests,
    dev rigs) a big-graph compile per bucket would outlast the run, so
    only the smallest warms here and the next few the scheduler can
    reach on a background thread.  Returns ``aot_prewarm``'s report with
    ``cold_start_s`` beside it."""
    if path.mode != "jax" or path.raw is None:
        return None
    from eges_tpu.crypto.aotstore import default_store
    from eges_tpu.utils.metrics import DEFAULT as metrics

    store = default_store()
    on_chip = path.platform == "tpu"
    cap = path.verifier.max_batch
    every = tuple(16 << i for i in range(16) if 16 << i <= cap)
    t0 = time.monotonic()
    info = dict(path.raw.aot_prewarm(
        buckets=every if on_chip else (16,), store=store))
    info["cold_start_s"] = round(time.monotonic() - t0, 3)
    metrics.gauge("verifier.cold_start_seconds").set(info["cold_start_s"])
    log("verifier warmup", dt=info["cold_start_s"],
        buckets=info["buckets"], aot_loads=info["aot_loads"],
        aot_compiles=info["aot_compiles"])
    later = tuple(b for b in (32, 64, 128) if b <= cap)
    if not on_chip and later:
        path.raw.aot_prewarm(buckets=later, store=store, background=True)
    return info
