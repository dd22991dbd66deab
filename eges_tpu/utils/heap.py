"""A serving process's warm heap, taken out of the collector's walk.

A full collection walks every container the collector tracks, and most
of what it tracks in a node is there before the first signature row is
served: jax, numpy, this package's modules, the deserialised executables
and their caches.  None of that can become garbage while the process
serves, and CPython walked it again at every full collection: 56-68 ms,
twice a second, with the GIL held (PERF.md section 5).  :func:`settle`
moves it into the collector's permanent generation ONCE, at the moment
the process's verify path first serves (the scheduler's dispatcher
thread as it starts; a sidecar client once it is connected), so that
every later full collection walks what was allocated since.

The collector itself is left as it is: it stays on, its thresholds stay
CPython's, and a cycle made after the freeze is collected as before.
What the freeze costs: an object alive at that moment that LATER becomes
cyclic garbage is never freed, which is bounded by what exists before
the first row; ``gc.collect()`` runs first, so nothing that is garbage
already is kept.

This module must stay importable WITHOUT JAX.
"""

from __future__ import annotations

import gc
import threading
import time

from eges_tpu.utils.metrics import DEFAULT as metrics

_lock = threading.Lock()
_settled = False


def settle() -> int | None:
    """Collect, then freeze what is left, the first time this process
    calls; the number of objects the permanent generation then holds
    (also the gauge ``process.gc_frozen_objects``, beside
    ``process.gc_settle_seconds``: what the collection and the freeze
    took).  ``None`` on every later call, which does nothing."""
    global _settled
    with _lock:
        if _settled:
            return None
        _settled = True
        # analysis: allow-determinism(what the one collection took goes into a gauge, never a journal)
        t0 = time.monotonic()
        gc.collect()
        gc.freeze()
        # analysis: allow-determinism(the same gauge)
        took = time.monotonic() - t0
        frozen = gc.get_freeze_count()
    metrics.gauge("process.gc_frozen_objects").set(frozen)
    metrics.gauge("process.gc_settle_seconds").set(took)
    return frozen
