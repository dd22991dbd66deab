"""Controls of the sidecar's deployment (``drivers/sidecar.py``): the
program with one stated guarantee, or one premise of the cell, broken.  A
run with ``--control <name>`` has to come out not correct, each by the
check that is its own; ``tests/`` keeps each as a test and PERF.md gives
the readings.

* ``accept_all``: ``control.AcceptAll`` around the sidecar's device
  verifier: a row whose signature is no signature yields a sender, for
  every client alike.  Breaks "an invalid signature yields no sender"; it
  is the control for ``wrong_answers`` / ``invalid_frames_not_refused``.
* ``in_process``: the nodes run as threads of the driver's own process,
  straight on the scheduler: no client, no socket, one GIL.  Breaks the
  configuration's ``layout`` (node processes of their own); it is the
  control for ``clients``, which reads 0 (and the share of the least
  client then has nothing to read).
* ``one_client``: the sidecar answers its FIRST client only; every window
  of the others comes back with its rows marked dead at once, so those
  nodes answer on their own hosts.  Breaks "every asker is served by the
  sidecar"; it is the control for ``client_rows_min_share_pct`` (0) and
  ``sidecar_fallback_rows``.
"""

from __future__ import annotations

from perfbench.control import AcceptAll

NAMES = ("accept_all", "in_process", "one_client")


def verify_path_of(name, mode: str, **scheduler_kwargs):
    """The sidecar's verify path with control ``name`` in place: the
    program's own ``verify_path.build`` unless the control stands between
    the device facade and its scheduler."""
    from eges_tpu.crypto import verify_path

    bare = verify_path.build(mode, **scheduler_kwargs)
    if name != "accept_all":
        return bare
    bare.verifier.close()  # the facade gets a scheduler of its own
    return verify_path.on_scheduler(
        verify_path.VerifyPath(mode, raw=AcceptAll(bare.raw),
                               platform=bare.platform), **scheduler_kwargs)


class _DeadWindow:
    """A window none of whose rows the sidecar answered."""

    cached = coalesced = 0

    def __init__(self, n: int):
        self.results = [RuntimeError("this sidecar serves one client")] * n

    def add_done_callback(self, fn) -> None:
        fn(self)


class _NoScheduler:
    """In the scheduler's place for a connection that is not served."""

    def __init__(self, max_batch: int):
        self.max_batch = max_batch

    def submit_window(self, hashes, sigs, priority="bulk"):
        return _DeadWindow(len(hashes))

    def kick(self) -> None:
        pass


def serve_of(name, sched, socket_path: str):
    """``sidecar.serve`` with control ``name`` in place."""
    from eges_tpu.crypto import sidecar

    if name != "one_client":
        return sidecar.serve(sched, socket_path)

    class OneClient(sidecar.SidecarServer):
        def scheduler_of(self, conn):
            return sched if conn.cid == 1 else _NoScheduler(sched.max_batch)

    return OneClient(sched, socket_path)
