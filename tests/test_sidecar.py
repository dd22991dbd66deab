"""The verify sidecar's guarantees where a run of the benchmark cannot
reach them (``crypto/sidecar.py``): every window gets its answer or its
sender is told (7), a lost sidecar is never hidden (8), a client that
dies, stalls or tears a frame costs the others nothing (9),
consensus-class rows of any client go before bulk rows of every client
(10); close and reconnect; the sidecar's own entry point and a node
service built against it.  Real Unix sockets; every wait has a limit.
"""

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from eges_tpu.crypto import sidecar as sc
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.utils.metrics import DEFAULT as metrics
from tests.test_scheduler import _arrays, _host_model, _sign_entries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAIT_S = 30.0


def _until(cond, what: str) -> None:
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


class Gate:
    """A verifier that keeps every window inside it until told, and
    remembers the batches it was handed, in order."""

    def __init__(self):
        self._inner = NativeBatchVerifier()
        self.open = threading.Event()
        self.open.set()
        self.batches: list = []

    def recover_addresses(self, sigs, hashes):
        self.batches.append([bytes(h) for h in np.asarray(hashes)])
        assert self.open.wait(WAIT_S)
        return self._inner.recover_addresses(sigs, hashes)


@pytest.fixture
def rig(tmp_path):
    """``(scheduler, server, gate, a maker of clients)``, closed after."""
    gate = Gate()
    sched = VerifierScheduler(gate, max_batch=16, window_ms=10_000.0)
    server = sc.serve(sched, str(tmp_path / "s.sock"), max_inflight=2)
    made = []

    def client(**kw):
        made.append(sc.SidecarClient(server.path, **kw))
        return made[-1]

    yield sched, server, gate, client
    gate.open.set()
    for c in made:
        c.close()
    server.close()
    sched.close()


def _raw(path: str) -> socket.socket:
    """A connection that speaks the protocol by hand."""
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(WAIT_S)
    s.connect(path)
    s.sendall(sc._HELLO_C.pack(sc.MAGIC, sc.VERSION, os.getpid()))
    magic, version, inflight, max_batch, frame_rows = sc._HELLO_S.unpack(
        sc._read_exact(s, sc._HELLO_S.size))
    assert (magic, version, inflight, max_batch, frame_rows) == (
        sc.MAGIC, sc.VERSION, 2, 16, sc.MAX_FRAME_ROWS)
    return s


def _frame(call_id: int, entries, klass: int = 0) -> bytes:
    h, s = _arrays(entries)
    return sc._REQ.pack(sc.MAGIC, call_id, len(entries), klass) \
        + h.tobytes() + s.tobytes()


def _reply(s: socket.socket) -> tuple:
    magic, call_id, n, cached, coalesced = sc._REP.unpack(
        sc._read_exact(s, sc._REP.size))
    body = bytes(sc._read_exact(s, n * 21))
    assert magic == sc.MAGIC
    return call_id, [body[n + 20 * i:n + 20 * i + 20] if body[i] == 1
                     else None for i in range(n)], cached, coalesced


def test_the_wire_is_the_arrays_and_answers_come_back_under_their_call_id(
        rig):
    sched, server, gate, _client = rig
    a, b = _sign_entries(5, salt=71), _sign_entries(3, salt=72)
    s = _raw(server.path)
    gate.open.clear()
    s.sendall(_frame(7, a) + _frame(9, b, klass=1))
    # both windows are in flight before either has an answer
    _until(lambda: sched.stats()["window_submits"] == 2, "two windows")
    assert (sched.stats()["window_rows_bulk"],
            sched.stats()["window_rows_consensus"]) == (5, 3)
    gate.open.set()
    got = dict((cid, ans) for cid, ans, _c, _j in (_reply(s), _reply(s)))
    assert got == {7: _host_model(a), 9: _host_model(b)}
    # asked again, the cache answers and the reply says so
    s.sendall(_frame(11, a[:2] + b[:1]))
    assert _reply(s) == (11, _host_model(a[:2] + b[:1]), 3, 0)
    s.close()
    _until(lambda: server.stats()["clients"] == 0, "the connection ended")
    assert server.stats()["served"][0]["rows"] == 11


@pytest.mark.parametrize("torn", ["magic", "rows", "class", "cut_header",
                                  "cut_body", "no_hello"])
def test_a_torn_frame_ends_its_connection_and_costs_the_others_nothing(
        torn, rig):
    sched, server, gate, client = rig
    good = client()
    entries = _sign_entries(6, salt=73)
    before = metrics.counter("sidecar.torn_frames").value
    if torn == "no_hello":
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(WAIT_S)
        s.connect(server.path)
        s.sendall(b"GET / HTTP/1.1\r\n\r\n")
    else:
        s = _raw(server.path)
        whole = _frame(1, entries)
        s.sendall({
            "magic": b"XXXX" + whole[4:],
            "rows": sc._REQ.pack(sc.MAGIC, 1, sc.MAX_FRAME_ROWS + 1, 0),
            "class": sc._REQ.pack(sc.MAGIC, 1, 6, 9) + whole[20:],
            "cut_header": whole[:11],
            "cut_body": whole[:200]}[torn])
        if torn.startswith("cut"):
            s.shutdown(socket.SHUT_WR)  # the stream ends inside a frame
    # the sidecar hangs up on it (a reset where it left bytes unread) ...
    try:
        assert s.recv(1 << 16) == b""
    except ConnectionResetError:
        pass
    s.close()
    _until(lambda: server.stats()["torn_frames"] == 1, "counted")
    assert metrics.counter("sidecar.torn_frames").value == before + 1
    # ... nothing of it reached the scheduler, and the client beside it
    # is answered as if nothing had happened
    assert sched.stats()["window_submits"] == 0
    assert list(good.recover_signers(entries)) == _host_model(entries)
    assert good.stats()["fallback_rows"] == 0


def test_a_client_killed_with_windows_in_flight_costs_the_others_no_answer(
        rig):
    sched, server, gate, client = rig
    doomed_rows = _sign_entries(8, salt=74)
    mine = _sign_entries(4, salt=75)
    gate.open.clear()
    s = _raw(server.path)
    s.sendall(_frame(1, doomed_rows[:4]) + _frame(2, doomed_rows[4:]))
    _until(lambda: sched.stats()["window_submits"] == 2, "in flight")
    # it dies without a word; its windows are still on their way
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 struct.pack("ii", 1, 0))
    s.close()
    other = client()
    answer: list = []
    t = threading.Thread(target=lambda: answer.append(
        other.recover_signers(mine, priority="consensus")))
    t.start()
    _until(lambda: sched.stats()["window_submits"] == 3, "the other's")
    gate.open.set()
    t.join(WAIT_S)
    assert not t.is_alive() and list(answer[0]) == _host_model(mine)
    _until(lambda: server.stats()["clients"] == 1, "the dead one is gone")
    # the dead client's rows were computed all the same and wait in the
    # cache: nothing hangs, nothing leaks
    assert sched.stats()["pending"] == 0
    again = other.recover_signers(doomed_rows)
    assert list(again) == _host_model(doomed_rows) and again.cached == 8


def test_a_reader_that_stops_is_held_at_its_bound_while_others_go_on(rig):
    sched, server, gate, client = rig
    flood = [_sign_entries(2, salt=80 + i) for i in range(6)]
    gate.open.clear()
    s = _raw(server.path)
    for i, entries in enumerate(flood):
        s.sendall(_frame(i + 1, entries))
    # two windows of it are in flight (max_inflight), the rest unread
    _until(lambda: server.stats()["backpressure_waits"] >= 1, "held")
    time.sleep(0.05)
    assert sched.stats()["window_submits"] == 2
    other = client()
    mine = _sign_entries(3, salt=90)
    answer: list = []
    t = threading.Thread(target=lambda: answer.append(
        other.recover_window(*_arrays(mine))))
    t.start()
    # the other client's window enters past the flooder's
    _until(lambda: sched.stats()["window_submits"] == 3, "not held")
    gate.open.set()
    t.join(WAIT_S)
    assert not t.is_alive() and list(answer[0]) == _host_model(mine)
    # and once the flooder reads, every one of its frames is answered
    got = dict((cid, ans) for cid, ans, _c, _j in
               (_reply(s) for _ in flood))
    assert got == {i + 1: _host_model(e) for i, e in enumerate(flood)}
    s.close()


def test_consensus_rows_of_any_client_go_before_bulk_rows_of_every_client(
        rig):
    sched, server, gate, client = rig
    bulk_a, bulk_b = _sign_entries(14, salt=91), _sign_entries(14, salt=92)
    votes = _sign_entries(6, salt=93)
    first = _sign_entries(2, salt=94)  # one row would stay on the host
    gate.open.clear()
    a, b, c = client(), client(), client()
    answers: dict = {}

    def ask(name, cl, entries, priority):
        answers[name] = list(cl.recover_signers(entries, priority=priority))

    # a window is inside the verifier, so what follows queues behind it
    threads = [threading.Thread(target=ask, args=(
        "first", a, first, "bulk"))]
    threads[0].start()
    _until(lambda: len(gate.batches) == 1, "the first window went out")
    for name, cl, entries, prio in (("a", a, bulk_a, "bulk"),
                                    ("b", b, bulk_b, "bulk"),
                                    ("c", c, votes, "consensus")):
        threads.append(threading.Thread(target=ask, args=(
            name, cl, entries, prio)))
        threads[-1].start()
        _until(lambda n=len(threads): sched.stats()["window_submits"] == n,
               name)
    gate.open.set()
    for t in threads:
        t.join(WAIT_S)
    assert not any(t.is_alive() for t in threads)
    assert answers == {"first": _host_model(first),
                       "a": _host_model(bulk_a), "b": _host_model(bulk_b),
                       "c": _host_model(votes)}
    # 34 rows were pending for a 16-row cap: the votes, which came LAST
    # and from another client, are all in the next batch
    nxt = gate.batches[1]
    assert len(nxt) == 16 and {h for h, _s in votes} <= set(nxt)


def test_a_lost_sidecar_is_answered_on_the_host_counted_and_found_again(
        tmp_path):
    path = str(tmp_path / "s.sock")
    entries = _sign_entries(40, salt=95)
    want = _host_model(entries)
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    server = sc.serve(sched, path)
    clients = [sc.SidecarClient(path) for _ in range(3)]
    stop, wrong, asked = threading.Event(), [], [0, 0, 0]
    before = metrics.counter("sidecar.fallback_rows").value

    def node(i: int) -> None:
        k = 0
        while not stop.is_set():
            lo = (7 * k + 3 * i) % 30
            got = clients[i].recover_signers(entries[lo:lo + 10],
                                             priority="consensus")
            wrong.extend(j for j, g in enumerate(got) if g != want[lo + j])
            asked[i] += 10
            k += 1

    threads = [threading.Thread(target=node, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    _until(lambda: min(asked) >= 50, "under load")
    # the sidecar goes away under load ...
    server.close()
    sched.close()
    marks = list(asked)
    _until(lambda: all(a >= m + 50 for a, m in zip(asked, marks)),
           "the nodes go on")
    lost = [c.stats() for c in clients]
    # ... and comes back at the same path: the clients find it
    sched2 = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    server2 = sc.serve(sched2, path)
    _until(lambda: server2.stats()["clients"] == 3, "reconnected")
    marks = [c.stats()["fallback_rows"] for c in clients]
    time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(WAIT_S)
    try:
        assert not any(t.is_alive() for t in threads)
        assert wrong == []  # every row answered, and rightly
        for st in lost:
            # (a call answered as the sidecar went may be counted late)
            assert st["fallback_rows"] >= 40 and st["lost"] >= 1
            assert not st["connected"]
        fell = sum(c.stats()["fallback_rows"] for c in clients)
        assert metrics.counter("sidecar.fallback_rows").value \
            == before + fell
        for c in clients:
            st = c.stats()
            assert st["connected"] and st["connects"] == 2
            assert st["windows"] * 10 == st["rows"]
        # once it is back nothing falls back any more
        assert [c.stats()["fallback_rows"] for c in clients] == marks
        assert server2.stats()["rows"] > 0
    finally:
        for c in clients:
            c.close()
        server2.close()
        sched2.close()
    # a closed client still answers, on the host, and says so
    got = clients[0].recover_window(*_arrays(entries[:4]))
    assert list(got) == want[:4]
    assert clients[0].stats()["fallback_rows"] >= lost[0]["fallback_rows"] + 4


def test_a_window_the_sidecars_scheduler_failed_is_told_and_recovered(
        tmp_path):
    """Rows that die with their window come back marked dead, never as
    "no signer": the client recovers them on its host."""

    class Broken:
        def recover_addresses(self, sigs, hashes):
            raise RuntimeError("the device is gone")

    sched = VerifierScheduler(Broken(), max_batch=16)
    # the scheduler's own divert would rescue the window; take it away
    sched._host_recover_rows = lambda keys: (_ for _ in ()).throw(
        RuntimeError("and so is the host path"))
    server = sc.serve(sched, str(tmp_path / "s.sock"))
    client = sc.SidecarClient(server.path)
    entries = _sign_entries(5, salt=96)
    try:
        got = client.recover_signers(entries)
        fut = client.submit(*entries[0])
        assert list(got) == _host_model(entries)
        assert fut.result(WAIT_S) == _host_model(entries[:1])[0]
        st = client.stats()
        assert st["fallback_rows"] == 6 and st["connected"]
        assert server.stats()["served"][0]["rows"] == 0  # answered none
    finally:
        client.close()
        server.close()
        sched.close()


def test_many_threads_on_few_clients_lose_no_call_and_no_count(tmp_path):
    """More callers than cores on three clients, the interpreter made to
    switch threads every few bytecodes: every call gets ITS answer, and
    the counts on both sides of the socket are the calls made (a lost
    update or a reply handed to the wrong call would break one)."""
    entries = _sign_entries(48, salt=99)
    want = _host_model(entries)
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    server = sc.serve(sched, str(tmp_path / "s.sock"), max_inflight=2)
    clients = [sc.SidecarClient(server.path) for _ in range(3)]
    wrong, made = [], [0] * 16
    t_stop = time.monotonic() + 2.0

    def caller(j: int) -> None:
        k = j
        while time.monotonic() < t_stop:
            lo, n = (5 * k) % 40, 1 + k % 8
            got = clients[j % 3].recover_window(
                *_arrays(entries[lo:lo + n]),
                priority="consensus" if k % 3 == 0 else "bulk")
            if list(got) != want[lo:lo + n]:
                wrong.append((j, lo, n))
            made[j] += n
            k += 7

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(j,))
                   for j in range(len(made))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
        assert not any(t.is_alive() for t in threads)
        _until(lambda: server.stats()["rows"] == sum(made), "counted")
        st = [c.stats() for c in clients]
        sv = server.stats()
    finally:
        sys.setswitchinterval(was)
        for c in clients:
            c.close()
        server.close()
        sched.close()
    assert wrong == [] and sum(made) > 500
    assert [s["rows"] for s in st] == [
        sum(made[j] for j in range(len(made)) if j % 3 == i)
        for i in range(3)]
    assert sum(s["fallback_rows"] + s["lost"] for s in st) == 0
    assert sv["windows"] == sum(s["windows"] for s in st)
    assert sv["backpressure_waits"] > 0  # 16 callers, 2 slots a client
    assert sched.stats()["window_rows"] == sum(made)


def _wait_for_socket(path: str, proc) -> None:
    """Until the sidecar ``proc`` takes connections at ``path`` (the
    file is there from the bind, a moment before the listen)."""
    def listening() -> bool:
        if proc.poll() is not None:
            return True
        probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        probe.settimeout(1.0)
        try:
            probe.connect(path)
        except OSError:
            return False
        finally:
            probe.close()
        return True

    _until(listening, "the sidecar's socket")
    assert proc.poll() is None, proc.stdout.read()


def test_the_entry_point_serves_and_a_killed_sidecar_is_not_hidden(
        tmp_path):
    path = str(tmp_path / "verify.sock")
    cmd = [sys.executable, "-m", "eges_tpu.crypto.sidecar", "--socket",
           path, "--verifier", "native"]
    entries = _sign_entries(12, salt=97)
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    client = None
    try:
        _wait_for_socket(path, proc)
        client = sc.SidecarClient(path)
        assert client.stats()["connected"] and client.max_batch == 1024
        assert list(client.recover_signers(entries)) == _host_model(entries)
        assert client.stats()["fallback_rows"] == 0
        proc.send_signal(signal.SIGKILL)
        proc.wait(WAIT_S)
        more = _sign_entries(5, salt=98)
        assert list(client.recover_signers(more)) == _host_model(more)
        assert client.stats()["fallback_rows"] == 5
        # a sidecar started again at the path is found by the next call
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        _wait_for_socket(path, proc)
        time.sleep(sc.RECONNECT_S)
        again = client.recover_signers(more)
        assert list(again) == _host_model(more)
        assert client.stats()["fallback_rows"] == 5
        assert client.stats()["connects"] == 2
        # SIGTERM is a clean stop: the log says what was served
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=WAIT_S)
        assert proc.returncode == 0 and "sidecar stopped" in out
        assert not os.path.exists(path)
    finally:
        if client is not None:
            client.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(WAIT_S)


NODE = """
import asyncio, json, sys
from eges_tpu.node.service import NodeService, ServiceConfig
from eges_tpu.crypto.sidecar import SidecarClient

async def run():
    svc = NodeService(ServiceConfig(
        datadir=sys.argv[1], genesis_path=sys.argv[2], key_hex="07" * 32,
        verifier_mode="sidecar", sidecar_path=sys.argv[3], mine=False))
    v = svc.chain.verifier
    assert isinstance(v, SidecarClient) and svc.txpool.verifier is v
    assert svc.node.verifier is v
    from eges_tpu.crypto import verify_path
    assert verify_path.warm(svc._verify_path) is None
    print("@@", json.dumps({"jax": [m for m in sys.modules
                                     if m == "jax" or m.startswith("jax.")],
                            "stats": v.stats()}))
    v.close()

asyncio.run(run())
"""


def test_a_node_service_on_a_sidecar_holds_a_client_and_imports_no_jax(
        tmp_path):
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=64)
    server = sc.serve(sched, str(tmp_path / "s.sock"))
    gen = tmp_path / "genesis.json"
    gen.write_text(json.dumps({"config": {"thw": {}}, "timestamp": "0x0"}))
    try:
        p = subprocess.run(
            [sys.executable, "-c", NODE, str(tmp_path / "d"), str(gen),
             server.path], cwd=REPO, capture_output=True, text=True,
            timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        said = json.loads(next(ln for ln in p.stdout.splitlines()
                               if ln.startswith("@@"))[2:])
        assert said["jax"] == []
        assert said["stats"]["connected"] and said["stats"]["max_batch"] == 64
        assert server.stats()["connections"] == 1
        assert server.stats()["served"][0]["pid"] != os.getpid()
    finally:
        server.close()
        sched.close()


def test_the_node_takes_the_sidecars_flags():
    from eges_tpu.node.__main__ import build_parser

    args = build_parser().parse_args(
        ["--datadir", "d", "--genesis", "g", "--keyhex", "00",
         "--verifier", "sidecar", "--sidecar", "/run/eges/verify.sock"])
    assert (args.verifier, args.sidecar) == ("sidecar",
                                             "/run/eges/verify.sock")
    from eges_tpu.crypto import verify_path
    with pytest.raises(ValueError):
        verify_path.build("sidecar")  # no path: said, not guessed
