"""The verify sidecar: ONE process of a host holds the chip, the
coalescing scheduler and the recovery cache, and every node process of
that host hands it its signature windows over a local stream socket.

A chip belongs to one process at a time.  Before this module a host's
nodes had two choices: one node on the chip and the others on the host's
C++ verifier, or one node a host.  The sidecar is the layout the system
was specified for (``BASELINE.json``'s north star: signatures marshaled
to a sidecar that runs recover on the TPU): its process builds exactly
what a node on the chip builds for itself (``crypto/verify_path.py``:
``default_verifier()`` behind ``scheduler_for()``, every bucket warmed)
and :func:`serve` puts the scheduler's window entry on a Unix socket; a
node started with ``--verifier sidecar --sidecar PATH`` holds a
:class:`SidecarClient` where it would hold the scheduler, imports no
jax, and its pool, ``recover_senders`` and ``QuorumTally`` find the
methods they look for by duck type (``recover_window``,
``recover_signers``, ``recover_addresses``, ``submit``, ``max_batch``,
``stats``, ``close``).

**The wire** is the arrays the window entry already takes, as they lie
in memory; no pickle, no JSON, nothing a row but the list of answers the
scheduler itself keeps.  All integers little-endian::

    hello    client -> server   MAGIC, version u16, pad, pid u32
    hello    server -> client   MAGIC, version u16, windows in flight u16,
                                max_batch u32, rows a frame u32
    request  MAGIC, call id u64, rows u32, class u8 (0 bulk, 1
             consensus), pad; then hashes rows x 32, sigs rows x 65
    reply    MAGIC, call id u64, rows u32, cached u32, coalesced u32;
             then ok rows x 1 (0 no signer, 1 an address, 2 the row died
             with its window), addrs rows x 20

**The server** runs two threads a connection.  The reader takes a slot
(at most :data:`MAX_INFLIGHT` windows of one connection are in flight:
while all are taken it reads nothing, so a client cannot grow the
sidecar's memory, and the socket's own buffers push back on the
client), reads one frame, enters it through
``VerifierScheduler.submit_window`` with the client's priority, kicks,
and goes on to the next frame WITHOUT waiting: several windows of several
clients are in flight, a consensus-class window of any client goes
before bulk rows of every client exactly as inside one process, and rows
of different clients that are the same key meet in one cache and one
in-flight table.  The window's completion (on a lane worker's thread)
only queues it; the connection's writer builds the reply, sends it and
gives the slot back.  A client that stops reading blocks its own writer
and, through the slots, its own reader; the lane workers and the other
connections never touch its socket.  A frame that is torn (wrong magic,
more rows than a frame may hold, an end of stream inside a frame) ends
ITS connection after the windows it had in flight have resolved; nothing
is dropped in silence and nobody else loses an answer.

**The client** numbers its calls, sends under one lock, and one reader
thread hands each reply to the call that waits for it, so the threads of
a node (block workers, the pool's timer) have several windows in flight.
Every row gets an answer: one the sidecar gave, or, where the sidecar is
gone, closed, too slow (:data:`CALL_S`) or reported the row dead,
the host's native path (``scheduler.host_recover_rows``, as a torn-down
scheduler's rows are answered), counted in ``sidecar.fallback_rows``: a
lost sidecar is slow, loud and never wrong.  It reconnects on a later
call.

What the socket cuts: the submitter's trace id and ledger origin
(``_enter_window`` captures them from the calling thread) do not cross
it, so a sidecar's flight entries carry no transaction trace.

This module must stay importable WITHOUT JAX: the client side runs in
node processes that never load it; only :func:`main` (the sidecar's own
process) builds a device path.
"""

from __future__ import annotations

import itertools
import os
import queue
import socket
import struct
import threading
import time
from concurrent.futures import Future

import numpy as np

from eges_tpu.crypto.scheduler import (RecoveredAddresses, WindowAnswers,
                                       _class_of, _row_results,
                                       host_recover_rows)
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics

MAGIC = b"EGSV"
VERSION = 1
_HELLO_C = struct.Struct("<4sHxxI")
_HELLO_S = struct.Struct("<4sHHII")
_REQ = struct.Struct("<4sQIBxxx")
_REP = struct.Struct("<4sQIII")
_CLASSES = ("bulk", "consensus")
OK_NONE, OK_ADDR, OK_DEAD = 0, 1, 2
_ZERO20 = bytes(20)

# Windows one connection may have in flight: the reader reads no further
# frame while this many have no reply written yet.
MAX_INFLIGHT = 8
# Rows one frame may hold (6.2 MB of request): a client cuts a larger
# call into frames, a header that claims more is a torn frame.
MAX_FRAME_ROWS = 1 << 16
# A closing connection waits this long for each window it still has in
# flight before it gives the socket up.
DRAIN_S = 30.0
# A client asks a lost sidecar again no sooner than this after its last
# attempt.
RECONNECT_S = 0.5
# A connection that says no hello for this long is no client's, and a
# connect or a hello that takes a client this long has failed.
HELLO_S = 5.0
# A call the sidecar has not answered after this long is lost to the
# client, which drops the connection and answers on its own host.
CALL_S = 60.0


class TornFrame(Exception):
    """What arrived is no frame of this protocol."""


def _read_exact(sock: socket.socket, n: int, *, at_boundary: bool = False):
    """``n`` bytes of the stream; None for an end of stream before the
    first of them where ``at_boundary`` says a frame may end there, a
    :class:`TornFrame` for one anywhere else."""
    buf = bytearray(n)
    view, got = memoryview(buf), 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            if got == 0 and at_boundary:
                return None
            raise TornFrame(f"end of stream {got} bytes into {n}")
        got += k
    return buf


# -- the server ---------------------------------------------------------

class _Connection:
    """One client of the sidecar: its socket, its slots, its two
    threads.  ``rows`` / ``windows`` / ``connected`` are guarded by the
    server's lock."""

    def __init__(self, server: "SidecarServer", sock: socket.socket,
                 cid: int):
        self.server, self.sock, self.cid = server, sock, cid
        self.pid = 0
        self.rows = 0
        self.windows = 0
        self.connected = True
        self._slots = threading.BoundedSemaphore(server.max_inflight)
        # resolved windows on their way to the writer: at most one a
        # slot and the reader's closing None, so a put never blocks
        self._outbox: queue.Queue = queue.Queue(server.max_inflight + 1)
        self._broken = False  # the writer's alone
        self.threads = [
            threading.Thread(target=self._recv_loop, daemon=True,
                             name=f"sidecar-recv-{cid}"),
            threading.Thread(target=self._reply_loop, daemon=True,
                             name=f"sidecar-reply-{cid}")]

    def _hello(self) -> bool:
        """The two hellos; False for a peer that left without a word
        (a probe of the socket), which is no fault of anybody's."""
        self.sock.settimeout(HELLO_S)
        try:
            head = _read_exact(self.sock, _HELLO_C.size, at_boundary=True)
        except socket.timeout:
            raise TornFrame("no hello") from None
        if head is None:
            return False
        magic, version, pid = _HELLO_C.unpack(head)
        if magic != MAGIC or version != VERSION:
            raise TornFrame(f"hello {bytes(magic)!r} version {version}")
        self.sock.settimeout(None)
        self.sock.sendall(_HELLO_S.pack(
            MAGIC, VERSION, self.server.max_inflight,
            self.server.scheduler.max_batch, MAX_FRAME_ROWS))
        with self.server._lock:
            self.pid = pid
        return True

    def _read_frame(self):
        """``(call id, rows, class, body)`` of the next request; None at
        an end of stream between frames."""
        head = _read_exact(self.sock, _REQ.size, at_boundary=True)
        if head is None:
            return None
        magic, call_id, n, klass = _REQ.unpack(head)
        if magic != MAGIC or klass >= len(_CLASSES) or \
                not 0 < n <= MAX_FRAME_ROWS:
            raise TornFrame(f"header {bytes(magic)!r} rows {n} "
                            f"class {klass}")
        with tracing.DEFAULT.span("sidecar.recv", rows=n):
            body = _read_exact(self.sock, n * 97)
        return call_id, n, klass, body

    def _enter(self, call_id: int, n: int, klass: int, body) -> None:
        """One request into the scheduler; the answer follows when the
        window resolves."""
        hashes = np.frombuffer(body, np.uint8, n * 32).reshape(n, 32)
        sigs = np.frombuffer(body, np.uint8, n * 65,
                             offset=n * 32).reshape(n, 65)
        sched = self.server.scheduler_of(self)
        t_in = time.monotonic()
        win = sched.submit_window(hashes, sigs, _CLASSES[klass])
        sched.kick()
        metrics.counter("sidecar.bytes_in").inc(_REQ.size + n * 97)
        win.add_done_callback(lambda w: self._outbox.put(
            (call_id, w, t_in, time.monotonic())))

    def _recv_loop(self) -> None:  # thread-entry
        slots = self._slots
        try:
            frame = self._hello()  # False: nobody there, nothing to read
            while frame:
                if not slots.acquire(blocking=False):
                    metrics.counter("sidecar.backpressure_waits").inc()
                    self.server._count("backpressure_waits")
                    slots.acquire()
                entered = False
                try:
                    frame = self._read_frame()
                    if frame is not None:
                        self._enter(*frame)
                        entered = True
                finally:
                    if not entered:
                        slots.release()
        except TornFrame as e:
            metrics.counter("sidecar.torn_frames").inc()
            self.server._count("torn_frames")
            self.server.log("sidecar torn frame", client=self.cid,
                            pid=self.pid, what=str(e))
        except OSError:
            pass  # the connection was reset or the server is closing
        finally:
            # the windows in flight still resolve, and their replies go
            # out if the socket takes them: the slots come back one a
            # reply, and with all of them the writer has nothing left
            for _ in range(self.server.max_inflight):
                if not slots.acquire(timeout=DRAIN_S):
                    break
            self._outbox.put(None)

    def _reply_loop(self) -> None:  # thread-entry
        try:
            while True:
                item = self._outbox.get()
                if item is None:
                    return
                try:
                    self._reply(*item)
                finally:
                    self._slots.release()
        finally:
            self.server._gone(self)

    def _reply(self, call_id: int, win, t_in: float, t_done: float) -> None:
        results = win.results
        n = len(results)
        metrics.histogram("sidecar.served_seconds").observe(t_done - t_in)
        if self._broken:
            return  # nobody is left to tell
        with tracing.DEFAULT.span("sidecar.reply", rows=n):
            ok = bytes([OK_ADDR if isinstance(r, bytes)
                        else OK_NONE if r is None else OK_DEAD
                        for r in results])
            addrs = b"".join([r if isinstance(r, bytes) else _ZERO20
                              for r in results])
            try:
                self.sock.sendall(_REP.pack(MAGIC, call_id, n, win.cached,
                                            win.coalesced) + ok + addrs)
            except OSError:
                self._broken = True
                self.shutdown()  # the reader learns it from its recv
                return
        metrics.counter("sidecar.windows").inc()
        metrics.counter("sidecar.rows").inc(n)
        metrics.counter("sidecar.bytes_out").inc(_REP.size + n * 21)
        with self.server._lock:
            self.rows += n - ok.count(OK_DEAD)  # rows it was ANSWERED
            self.windows += 1

    def shutdown(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # already down


class SidecarServer:
    """A scheduler's window entry on a Unix stream socket; see the
    module's docstring.  ``log(kind, **fields)`` takes its few lines."""

    # how many connections that ended stay in ``stats()["served"]``
    KEEP_ENDED = 64

    def __init__(self, scheduler, path: str, *,
                 max_inflight: int = MAX_INFLIGHT, log=None):
        self.scheduler = scheduler
        self.path = path
        self.max_inflight = max(1, max_inflight)
        self.log = log or (lambda kind, **kw: None)
        self._lock = threading.Lock()
        self._conns: dict = {}   # guarded-by: _lock
        self._totals = {"connections": 0, "torn_frames": 0,
                        "backpressure_waits": 0}  # guarded-by: _lock
        self._ids = itertools.count(1)
        self._closing = threading.Event()
        if os.path.exists(path):
            os.unlink(path)  # a socket file an earlier sidecar left
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(path)
        listener.listen(64)
        listener.settimeout(0.25)  # accept() looks at _closing this often
        self._listener = listener
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name="sidecar-accept")
        self._thread.start()

    def _count(self, key: str) -> None:
        with self._lock:
            self._totals[key] += 1

    def scheduler_of(self, conn: _Connection):
        """What answers ``conn``'s windows: the one scheduler, for every
        connection alike."""
        return self.scheduler

    def _accept_loop(self) -> None:  # thread-entry
        while not self._closing.is_set():
            try:
                sock, _addr = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # the listener was closed
            conn = _Connection(self, sock, next(self._ids))
            with self._lock:
                self._conns[conn.cid] = conn
                self._totals["connections"] += 1
                ended = [c for c in self._conns.values() if not c.connected]
                for old in ended[:max(0, len(ended) - self.KEEP_ENDED)]:
                    del self._conns[old.cid]
                live = sum(c.connected for c in self._conns.values())
            metrics.gauge("sidecar.clients").set(live)
            for t in conn.threads:
                t.start()

    def _gone(self, conn: _Connection) -> None:
        """``conn``'s writer has ended: nothing of it is in flight."""
        try:
            conn.sock.close()
        except OSError:
            pass  # already closed
        with self._lock:
            conn.connected = False
            live = sum(c.connected for c in self._conns.values())
        metrics.gauge("sidecar.clients").set(live)

    def stats(self) -> dict:
        """Connected clients, and what each connection was served."""
        with self._lock:
            served = [{"client": c.cid, "pid": c.pid, "rows": c.rows,
                       "windows": c.windows, "connected": c.connected}
                      for c in self._conns.values()]
            out = dict(self._totals)
        out["clients"] = sum(s["connected"] for s in served)
        out["rows"] = sum(s["rows"] for s in served)
        out["windows"] = sum(s["windows"] for s in served)
        out["served"] = served
        return out

    def close(self, timeout: float = 5.0) -> None:
        """Stop accepting, end every connection (a client then answers
        on its own host) and join the threads.  The scheduler is its
        owner's to close."""
        self._closing.set()
        self._thread.join(timeout)
        self._listener.close()
        with self._lock:
            conns = list(self._conns.values())
        for conn in conns:
            conn.shutdown()
        for conn in conns:
            for t in conn.threads:
                if t.is_alive():
                    t.join(timeout)
        if os.path.exists(self.path):
            os.unlink(self.path)


def serve(scheduler, path: str, **kwargs) -> SidecarServer:
    """``scheduler``'s window entry served on the Unix socket ``path``;
    the server is listening when this returns."""
    return SidecarServer(scheduler, path, **kwargs)


# -- the client ---------------------------------------------------------

class _Call:
    """One request on the wire and the thread that waits for it."""

    __slots__ = ("n", "done", "ok", "addrs", "cached", "coalesced", "then")

    def __init__(self, n: int):
        self.n = n
        self.done = threading.Event()
        self.ok = None       # (n,) uint8 once answered; None: no answer
        self.addrs = None    # (n, 20) uint8
        self.cached = 0
        self.coalesced = 0
        self.then = None     # called once, after ``done`` is set

    def finish(self) -> None:
        self.done.set()
        if self.then is not None:
            self.then(self)


class _Answers:
    """What ``WindowAnswers`` reads of a window's holder."""

    __slots__ = ("results", "cached", "coalesced")

    def __init__(self, results: list, cached: int, coalesced: int):
        self.results, self.cached, self.coalesced = (results, cached,
                                                     coalesced)


class SidecarClient:
    """What a node holds in the scheduler's place; see the module's
    docstring.  Thread-safe: any thread calls, one reader thread hands
    the replies out."""

    def __init__(self, path: str):
        self.path = path
        # until a sidecar says otherwise: the scheduler's own default
        self.max_batch = 1024
        self._frame_rows = MAX_FRAME_ROWS
        # two locks: ``_send_lock`` makes a frame's bytes one piece on
        # the stream and is held across the blocking send (and a
        # connect); ``_lock`` guards the state below and is never held
        # across I/O, so the reader always gets at the calls table,
        # whatever back-pressure the senders meet
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._sock: socket.socket | None = None  # guarded-by: _lock
        self._calls: dict = {}                   # guarded-by: _lock
        self._ids = itertools.count(1)
        self._last_try = -RECONNECT_S            # guarded-by: _lock
        self._closed = False                     # guarded-by: _lock
        self._stats = {"windows": 0, "rows": 0, "cached": 0,
                       "coalesced": 0, "fallback_windows": 0,
                       "fallback_rows": 0, "connects": 0,
                       "lost": 0}                # guarded-by: _lock
        with self._send_lock:
            self._connected()

    # -- the connection ---------------------------------------------------

    def _connected(self):
        """The live socket, connecting first where there is none and the
        last attempt is long enough ago; None while the sidecar cannot
        be reached.  Caller holds ``self._send_lock``."""
        with self._lock:
            if self._sock is not None or self._closed:
                return self._sock
            # analysis: allow-determinism(reconnect pacing is real time by nature)
            now = time.monotonic()
            if now - self._last_try < RECONNECT_S:
                return None
            self._last_try = now
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            sock.settimeout(HELLO_S)
            sock.connect(self.path)
            sock.sendall(_HELLO_C.pack(MAGIC, VERSION, os.getpid()))
            magic, version, _inflight, max_batch, frame_rows = \
                _HELLO_S.unpack(_read_exact(sock, _HELLO_S.size))
            if magic != MAGIC or version != VERSION:
                raise TornFrame("the sidecar speaks another protocol")
            sock.settimeout(None)
        except (OSError, TornFrame):
            sock.close()
            return None
        with self._lock:
            if self._closed:
                sock.close()
                return None
            self.max_batch, self._frame_rows = max_batch, frame_rows
            self._sock = sock
            self._stats["connects"] += 1
        threading.Thread(target=self._read_loop, args=(sock,), daemon=True,
                         name="sidecar-client").start()
        return sock

    def _drop(self, sock) -> None:
        """``sock`` is lost: every call that waits on it gets no answer
        (and answers on the host).  A later call connects anew."""
        with self._lock:
            if self._sock is not sock:
                return  # dropped before, its calls finished then
            self._sock = None
            calls, self._calls = list(self._calls.values()), {}
            self._stats["lost"] += 1
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # already down
        sock.close()
        for call in calls:
            call.finish()

    def _read_loop(self, sock) -> None:  # thread-entry
        try:
            while True:
                head = _read_exact(sock, _REP.size, at_boundary=True)
                if head is None:
                    break
                magic, call_id, n, cached, coalesced = _REP.unpack(head)
                if magic != MAGIC or n > MAX_FRAME_ROWS:
                    break
                body = _read_exact(sock, n * 21)
                metrics.counter("sidecar.bytes_in").inc(_REP.size + n * 21)
                with self._lock:
                    call = self._calls.pop(call_id, None)
                if call is None or call.n != n:
                    break  # an answer nobody asked for
                call.ok = np.frombuffer(body, np.uint8, n)
                call.addrs = np.frombuffer(body, np.uint8, n * 20,
                                           offset=n).reshape(n, 20)
                call.cached, call.coalesced = cached, coalesced
                call.finish()
        except (OSError, TornFrame):
            pass  # the sidecar went away
        finally:
            self._drop(sock)

    def _send(self, hashes: bytes, sigs: bytes, n: int, priority: str,
              then=None) -> _Call:
        """One frame out; the call it returns is done when its reply (or
        the loss of the connection) has come."""
        call = _Call(n)
        call.then = then
        klass = _CLASSES.index(_class_of(priority))
        sent, lost = False, None
        with self._send_lock:
            sock = self._connected()
            if sock is not None:
                with self._lock:
                    # a reader may have dropped ``sock`` meanwhile: a
                    # call enters the table only of the live socket,
                    # whose drop will finish it
                    if self._sock is sock:
                        call_id = next(self._ids)
                        self._calls[call_id] = call
                        sent = True
            if sent:
                try:
                    sock.sendall(_REQ.pack(MAGIC, call_id, n, klass)
                                 + hashes + sigs)
                except OSError:
                    lost = sock
        if not sent:
            call.finish()
        elif lost is not None:
            self._drop(lost)  # finishes this call with the others
        else:
            metrics.counter("sidecar.bytes_out").inc(_REQ.size + n * 97)
        return call

    # -- answers ------------------------------------------------------------

    def _answered(self, call: _Call, hashes: bytes, sigs: bytes):
        """``(ok (n,) bool, addrs (n,20) uint8)`` of a call that is done,
        the rows the sidecar did not answer recovered on this host."""
        n = call.n
        if call.ok is None:
            dead = np.ones(n, bool)
            ok, addrs = np.zeros(n, bool), np.zeros((n, 20), np.uint8)
        else:
            dead = call.ok == OK_DEAD
            ok, addrs = call.ok == OK_ADDR, call.addrs
        n_dead = int(dead.sum())
        if n_dead:
            ok, addrs = ok.copy(), np.array(addrs)
            idx = np.flatnonzero(dead).tolist()
            keys = [(hashes[32 * i:32 * i + 32], sigs[65 * i:65 * i + 65])
                    for i in idx]
            for i, addr in zip(idx, host_recover_rows(keys)):
                if addr is not None:
                    ok[i], addrs[i] = True, np.frombuffer(addr, np.uint8)
            metrics.counter("sidecar.fallback_rows").inc(n_dead)
        with self._lock:
            self._stats["windows"] += 1
            self._stats["rows"] += n
            self._stats["cached"] += call.cached
            self._stats["coalesced"] += call.coalesced
            if n_dead:
                self._stats["fallback_windows"] += 1
                self._stats["fallback_rows"] += n_dead
        metrics.counter("sidecar.windows").inc()
        metrics.counter("sidecar.rows").inc(n)
        return ok, addrs

    def _window(self, hashes: bytes, sigs: bytes, n: int, priority: str):
        """A synchronous call of ``n`` rows, in frames of at most what
        the sidecar takes: ``(ok, addrs, cached, coalesced)``."""
        with tracing.DEFAULT.span("sidecar.call", rows=n,
                                  **{"class": _class_of(priority)}):
            step = self._frame_rows
            parts = [(hashes[32 * i:32 * (i + step)],
                      sigs[65 * i:65 * (i + step)], min(step, n - i))
                     for i in range(0, n, step)]
            calls = [self._send(h, s, k, priority) for h, s, k in parts]
            for call in calls:
                if not call.done.wait(CALL_S):
                    # the sidecar holds the call past all patience: it
                    # is lost to this client, which says so and goes on
                    with self._lock:
                        sock = self._sock
                    if sock is not None:
                        self._drop(sock)
                    call.done.wait()
            done = [self._answered(c, h, s)
                    for c, (h, s, _k) in zip(calls, parts)]
        if len(done) == 1:
            ok, addrs = done[0]
        else:
            ok = np.concatenate([d[0] for d in done])
            addrs = np.concatenate([d[1] for d in done])
        return (ok, addrs, sum(c.cached for c in calls),
                sum(c.coalesced for c in calls))

    # -- what a scheduler's holders call --------------------------------------

    def recover_window(self, hashes: np.ndarray, sigs: np.ndarray,
                       *, priority: str = "bulk") -> WindowAnswers:
        """``VerifierScheduler.recover_window`` over the socket: one
        20-byte address or None a row, with the window's ``cached`` and
        ``coalesced`` counts."""
        n = len(hashes)
        if n == 0:
            return WindowAnswers(_Answers([], 0, 0))
        if hashes.shape[1] != 32 or sigs.shape[1] != 65:
            raise ValueError("window arrays must be (n,32) and (n,65)")
        ok, addrs, cached, coalesced = self._window(
            np.ascontiguousarray(hashes, np.uint8).tobytes(),
            np.ascontiguousarray(sigs, np.uint8).tobytes(), n, priority)
        return WindowAnswers(_Answers(_row_results(addrs, ok), cached,
                                      coalesced))

    def recover_signers(self, entries, *, priority: str = "bulk") -> list:
        """``VerifierScheduler.recover_signers`` over the socket.  A
        malformed entry answers None here and is never sent."""
        sound = [i for i, (h, s) in enumerate(entries)
                 if len(s) == 65 and len(h) == 32]
        out: list = [None] * len(entries)
        if not sound:
            return WindowAnswers(_Answers(out, 0, 0))
        ok, addrs, cached, coalesced = self._window(
            b"".join([bytes(entries[i][0]) for i in sound]),
            b"".join([bytes(entries[i][1]) for i in sound]),
            len(sound), priority)
        for i, r in zip(sound, _row_results(addrs, ok)):
            out[i] = r
        return WindowAnswers(_Answers(out, cached, coalesced))

    def recover_addresses(self, sigs: np.ndarray, hashes: np.ndarray,
                          *, priority: str = "bulk"):
        """``VerifierScheduler.recover_addresses`` over the socket: the
        reply's arrays as they came."""
        n = sigs.shape[0]
        if n == 0:
            return np.zeros((0, 20), np.uint8), np.zeros((0,), bool)
        ok, addrs, cached, coalesced = self._window(
            np.ascontiguousarray(hashes, np.uint8).tobytes(),
            np.ascontiguousarray(sigs, np.uint8).tobytes(), n, priority)
        addrs = np.array(addrs)
        addrs[~ok] = 0
        return RecoveredAddresses(addrs, np.array(ok),
                                  _Answers([], cached, coalesced))

    def submit(self, sighash: bytes, sig: bytes,
               priority: str = "bulk") -> Future:
        """One row, not waited for: the future resolves to the signer's
        address or None (a malformed row at once)."""
        fut: Future = Future()
        h, s = bytes(sighash), bytes(sig)
        if len(h) != 32 or len(s) != 65:
            fut.set_result(None)
            return fut

        def answered(call: _Call) -> None:
            ok, addrs = self._answered(call, h, s)
            fut.set_result(addrs[0].tobytes() if ok[0] else None)

        self._send(h, s, 1, priority, then=answered)
        return fut

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
            out["connected"] = self._sock is not None
        out["max_batch"] = self.max_batch
        return out

    def close(self) -> None:
        """Leave the sidecar; calls that still wait answer on the host,
        and so does every call made afterwards."""
        with self._lock:
            self._closed = True
            sock = self._sock
        if sock is not None:
            self._drop(sock)


# -- the sidecar's own process ----------------------------------------------

def main(argv=None) -> int:
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        description="The verify sidecar of a host: holds the chip and "
                    "serves the node processes started with --verifier "
                    "sidecar --sidecar PATH")
    ap.add_argument("--socket", required=True,
                    help="path of the Unix socket to serve")
    ap.add_argument("--verifier", default="jax", choices=["jax", "native"],
                    help="what answers the rows: jax device batches "
                         "(default) or the host's native C++ batches")
    ap.add_argument("--verbosity", type=int, default=3)
    args = ap.parse_args(argv)

    from eges_tpu.crypto import verify_path
    from eges_tpu.utils.log import get_logger

    log = get_logger("geec.sidecar", args.verbosity).geec
    path = verify_path.build(args.verifier, log=log)
    verify_path.warm(path, log=log)
    server = serve(path.verifier, args.socket, log=log)
    log("sidecar serving", socket=args.socket, verifier=args.verifier,
        max_batch=path.verifier.max_batch)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    server.close()
    path.verifier.close()
    log("sidecar stopped", **{k: v for k, v in server.stats().items()
                              if k != "served"})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
