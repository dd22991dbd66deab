"""The PROPOSER of a large committee that builds, gets certified and seals
its own blocks, in one process that holds the chip (``drivers/proposer.py``
is the tally alone, ``c256``'s).

Builds what ``eges_tpu/node/service.py`` builds, as ``drivers/acceptor.py``
builds its node: the verify path of ``crypto/verify_path.py`` (the default
verifier behind the coalescing scheduler, every bucket warmed), a
``BlockChain`` over the genesis allocation, a ``GeecNode`` with
``mine=True`` on the wall clock with the chain's 1024 bootstrap members
and the node's own signing key, and a ``TxPool`` on the node's lock.  In
the transport's place stands an object that keeps what the node sends (its
``ElectMessage``s, its validate request, its confirm).  The node is GIVEN
its trusted random source as it is given its clock (``rand_source``):
the generator's, whose seeds put the node into every height's committee.
Height after height the node is handed what such a node receives
(``perfbench/gen_heights.py``, everything from ``--seed``), through the
calls a transport makes (``on_direct``) and the ingress calls
``drivers/acceptor.py`` uses:

* the height's gossip windows of raw transaction frames through
  ``decode_txn_window`` and ``admit_remotes_window``, on a feeder thread
  that runs one height ahead of the block path;
* once the node's ``ElectMessage``s of the height have left the transport,
  its committee's signed votes, back to back; the vote that brings the
  count back to the threshold after the forged one is pruned elects the
  node, and inside that very call the node builds its block
  (``pending_txns``, ``execute_preview``, the body's root), signs the
  request and gossips it;
* the request is read off the transport's BYTES by the plain reference
  (its length, its height, the block's hash), and the other 1023
  validators' ``ValidateReply`` datagrams, signed over THAT hash, follow
  back to back on the same thread; the reply that certifies the quorum
  arms the seal, which runs on the clock's timer thread (``backoff_time``
  0): the confirm, ``chain.offer``, the insert, the listener
  (``remove_included``, the working block moved on) and the next
  height's election, all the node's own.

The other 1023 validators and the clients are the generator; nothing
stands in for them inside the program.  ``correct`` is decided against
``perfbench/ref/`` alone, of what the run itself produced: every request
and confirm the node gossiped is read back from its bytes and every block
executed by the plain reference on the reference's own parent state.
"""

from __future__ import annotations

import concurrent.futures
import importlib
import json
import multiprocessing
import os
import random
import shutil
import sys
import tempfile
import threading
import time

from perfbench import control_propose, gen_heights, harness, peaks
from perfbench.clock import ThreadClock
from perfbench.drivers import validator
from perfbench.drivers import acceptor
from perfbench.drivers.node import Compiles, GcPauses, _no_span, _snapshot
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import rlp, secp
from perfbench.ref import senders as ref_senders
from perfbench.ref import state as ref_state
from perfbench.ref.keccak import keccak256_many

# readings this cell cannot list, for the ``info`` line: the older
# metrics whose ``workloads`` tests pin with ``==`` (the acceptor's list),
# and six of this cell's own suffix files, because BENCHMARK.json holds at
# most 128 per-layer metrics and had 116
UNLISTED = acceptor.UNLISTED + (
    "block_senders_ms.propose", "block_cached_share.propose",
    "block_execute_share.propose", "state_root_share.propose",
    "block_roots_share.propose", "quorum_verify_ms.propose")
VALIDATE_REQ, CONFIRM_BLOCK = 0x11, 0x15  # gossip-plane codes
UDP_ELECT = 0x02
WAIT_S = 30.0  # a step of a height that takes longer has failed


def _code(data: bytes) -> int:
    """The code of a gossip or direct message: its outer list's first
    item, one byte."""
    b = data[0]
    return data[1 if b < 0xF8 else 1 + (b - 0xF7)]


class Transport:
    """In the transport's place: keeps what the node sends."""

    def __init__(self):
        self.cv = threading.Condition()
        self.gossiped: list = []  # (when, bytes), oldest first
        self.direct: list = []    # datagrams, oldest first

    def gossip(self, data: bytes, **_kw) -> None:
        with self.cv:
            self.gossiped.append((time.monotonic(), data))
            self.cv.notify_all()

    def send_direct(self, ip: str, port: int, data: bytes, **_kw) -> None:
        with self.cv:
            self.direct.append(data)
            self.cv.notify_all()


class Tally(validator.Tally):
    """The validator's tally of the gossip path (every admission, every
    window handed over, judged after the window), and beside it what the
    node decided, height by height: taken from its journal as it writes
    it and from the chain as it inserts."""

    def __init__(self, feed: gen_heights.HeightsFeed):
        super().__init__(feed)
        self.node = None
        self.cv = threading.Condition()
        self.inserted: list = []   # (number, hash, was the head, when)
        self.built: dict = {}      # height -> transactions of its proposal
        self.elected: dict = {}    # height -> (votes handed, supporters)
        self.certified: dict = {}  # height -> (replies handed, counted)
        self.step_rows = 0         # rows of the block path given an answer
        self.votes_handed = 0      # of the height in hand
        self.acks_handed = 0
        self.acks_counted = 0      # those not from outside the membership

    def on_block(self, blk) -> None:
        with self.cv:
            self.inserted.append((blk.number, blk.hash,
                                  self.node.chain.head().hash == blk.hash,
                                  time.monotonic()))
            self.cv.notify_all()

    def on_event(self, ev: dict) -> None:
        """The journal's tap, under the node's lock.  A height's rows are
        fixed by its construction: the proposal's transactions once, the
        votes up to the one that elected, the ACKs that count up to the
        one that certified."""
        kind, h = ev["type"], ev.get("blk")
        if kind == "proposal_built":
            self.built[h] = ev["txns"]
            self.step_rows += ev["txns"]
        elif kind == "election_won":
            self.elected[h] = (self.votes_handed,
                               frozenset(self.node.wb.supporters))
            self.step_rows += self.votes_handed
        elif kind == "validate_quorum":
            self.certified[h] = (self.acks_handed, self.acks_counted)
            self.step_rows += self.acks_counted


class Heights:
    """The node, and the two threads that feed it."""

    def __init__(self, feed, node, pool, chain, transport, tally, annotate):
        self.feed, self.node, self.pool = feed, node, pool
        self.chain, self.transport = chain, transport
        self.tally, self.annotate = tally, annotate
        self.cv = threading.Condition()
        self.gossip_in = 0     # streams whose gossip is handed over
        self.started = 0       # heights whose block path has begun
        self.done = 0          # heights sealed
        self.stop = threading.Event()
        self.exhausted = threading.Event()
        self.go = threading.Event()       # the window is open
        self.closing = threading.Event()  # it closes with the height in hand
        self.closed = threading.Event()
        self.warm = 0                     # the stream it opens with
        self.failed = None                # the step that never came
        self.driver_s = 0.0    # the block path's own share: the replies
        #                        signed, the request's header read

    def feeder(self) -> None:
        """Gossip, one height ahead of the block path."""
        from eges_tpu.ingress import admit_remotes_window, decode_txn_window

        feed, handed = self.feed, 0
        for p in range(feed.heights):
            with self.cv:
                self.cv.wait_for(lambda: self.started >= p
                                 or self.stop.is_set())
            for idx in feed.windows(p):
                if self.stop.is_set():
                    return
                with self.annotate("decode_window"):
                    cols = decode_txn_window([feed.frames[k] for k in idx])
                with self.annotate("pool_admit"):
                    admit_remotes_window(self.pool, cols)
                self.tally.handed.append((p, idx))
                handed += len(idx)
            # a stream is IN when the pool has given its last frame an
            # outcome (the last short window waits for the pool's 5 ms
            # timer): a build must find the height's transfers admitted
            while sum(self.pool.stats[k] for k in (
                    "admitted", "rejected", "duplicate")) < handed \
                    and not self.stop.is_set():
                time.sleep(0.0005)
            with self.cv:
                self.gossip_in = p + 1
                self.cv.notify_all()

    def _sent(self, box: list, at: int, want) -> int:
        """Wait until ``want(item)`` holds for an item of the transport's
        ``box`` from index ``at`` on; its index, or -1."""
        tp, deadline = self.transport, time.monotonic() + WAIT_S
        with tp.cv:
            while True:
                for i in range(at, len(box)):
                    if want(box[i]):
                        return i
                at = len(box)
                left = deadline - time.monotonic()
                if left <= 0 or self.stop.is_set():
                    return -1
                tp.cv.wait(min(left, 0.25))

    def _never(self, step: str) -> None:
        """A step did not come in ``WAIT_S`` (a run that is being stopped
        waits for nothing and has not failed)."""
        if not self.stop.is_set():
            self.failed = step

    def block_path(self) -> None:
        """Votes, replies and the wait for the seal, a height at a time."""
        try:
            self._heights()
        finally:
            self.closed.set()

    def _heights(self) -> None:
        feed, node, tally, tp = self.feed, self.node, self.tally, \
            self.transport
        voters = len(feed.votes[0])
        direct_at = gossip_at = 0
        for p in range(feed.heights):
            h = p + 1
            with self.cv:
                self.cv.wait_for(lambda: self.gossip_in > p
                                 or self.stop.is_set())
                if self.stop.is_set():
                    return
            if p == self.warm:
                # the feeder starts the next stream when this height
                # starts, inside the window: a window then holds whole
                # heights, each with one stream of gossip
                self.go.wait()
            with self.cv:
                self.started = p + 1
                self.cv.notify_all()
            # -- the election: the node has asked its committee ------------
            seen = [0]

            def asked(dg, h=h, seen=seen) -> bool:
                if _code(dg) == UDP_ELECT and int.from_bytes(
                        ref_senders.read(ref_senders.read(dg)[2])[1],
                        "big") == h:
                    seen[0] += 1
                return seen[0] >= voters
            direct_at = self._sent(tp.direct, direct_at, asked) + 1
            if not direct_at:
                self._never(f"height {h}: no election")
                return
            tally.votes_handed = 0
            with self.annotate("election_votes"):
                for dg, _kind, _a in feed.votes[p]:
                    tally.votes_handed += 1
                    node.on_direct(dg)
            # -- the build was the node's own: its request, from bytes ------
            # (a retry of an earlier height's request may lie in between)
            got = []

            def requested(g, h=h, got=got) -> bool:
                if _code(g[1]) != VALIDATE_REQ:
                    return False
                got[:] = gen_heights.request_block(g[1])
                return got[0] == h
            t0 = time.monotonic()
            gossip_at = self._sent(tp.gossiped, gossip_at, requested) + 1
            if not gossip_at:
                self._never(f"height {h}: no request")
                return
            with self.annotate("ack_sign"):
                replies = feed.acks(p, got[1])
            self.driver_s += time.monotonic() - t0
            # -- the other 1023 answer at once -------------------------------
            tally.acks_handed = tally.acks_counted = 0
            with self.annotate("ack_replies"):
                for dg, (_a, kind, _s) in zip(replies, feed.ack_plan[p]):
                    tally.acks_handed += 1
                    tally.acks_counted += kind != "non_member"
                    node.on_direct(dg)
            # -- the seal is the node's own too --------------------------------
            with tally.cv:
                sealed = tally.cv.wait_for(
                    lambda: len(tally.inserted) > p or self.stop.is_set(),
                    WAIT_S)
            if not sealed or self.stop.is_set():
                self._never(f"height {h}: not sealed")
                return
            with self.cv:
                self.done = p + 1
                self.cv.notify_all()
            if self.closing.is_set():
                return
        self.exhausted.set()

    def outcomes(self) -> int:
        """Rows whose results have come back so far."""
        s = self.pool.stats
        return (s["admitted"] + s["rejected"] + s["duplicate"]
                + self.tally.step_rows)


def _unlisted(obs: dict) -> dict:
    out = {}
    for name in UNLISTED:
        spec = harness.metric_file(name)
        value = importlib.import_module(
            "perfbench.readers." + spec["reader"]).read(obs, **spec["args"])
        if value is not None:
            out[name] = round(value, 2)
    return out


def build_node(feed, d: dict, chain, sched, transport, cls=None):
    """The node as ``node/service.py`` wires one, with ``mine=True``: the
    chain's bootstrap members, the node's own key, the wall clock, and the
    generator as its trusted random source."""
    from eges_tpu.consensus.config import (BootstrapNode, ChainGeecConfig,
                                           NodeConfig)
    from eges_tpu.consensus.node import GeecNode

    me = {a: (ip, port) for a, ip, port in feed.validators}[feed.node_addr]
    ncfg = NodeConfig(
        coinbase=feed.node_addr, consensus_ip=me[0], consensus_port=me[1],
        n_candidates=d["committee"], n_acceptors=d["acceptors"],
        txn_per_block=d["txn_per_block"], txn_size=d["payload_bytes"],
        total_nodes=d["validators"],
        privkey=feed.node_priv.to_bytes(32, "big"))
    ccfg = ChainGeecConfig(bootstrap=tuple(
        BootstrapNode(account=a, ip=ip, port=port)
        for a, ip, port in feed.validators))
    return (cls or GeecNode)(chain, ThreadClock(), transport, ncfg, ccfg,
                             mine=True, verifier=sched,
                             rand_source=feed.thw)


# -- the plain reference's reading of what the node sent ------------------

def read_request(data: bytes) -> dict:
    """In a worker: a validate request's bytes by the reference alone.
    What does not depend on the parent state: the block's hash, its
    header's commitments, its transactions' encodings' hashes and root,
    and each transfer without its sender."""
    code, fields = ref_senders.read(data)
    header, fakes, geecs, txs = fields[2][:4]
    frames = [rlp.encode(t) for t in txs]
    num = lambda b: int.from_bytes(b, "big")  # noqa: E731
    return {"code": num(code), "height": num(fields[0]),
            "author": fields[1], "version": num(fields[6]),
            "sig": fields[8],
            "hash": ref_state.keccak256(rlp.encode(header)),
            "coinbase": header[2], "root": header[3], "tx_hash": header[4],
            "receipt_hash": header[5], "bloom": header[6],
            "gas_used": num(header[10]), "trust_rand": num(header[16]),
            "fakes": len(fakes), "geecs": len(geecs),
            "tx_hashes": keccak256_many(frames),
            "tx_root": ref_state.derive_sha(frames) if frames
            else ref_state.EMPTY_ROOT,
            "transfers": [(num(t[0]), t[3], num(t[4]), num(t[2]))
                          for t in txs],
            "first": frames[:2]}


def state_roots(addrs: list, accounts: list, gas: list) -> tuple:
    """In a worker: the state root over every account and the receipts'
    root of a block whose transfers' cumulative gas is ``gas``."""
    keys = dict(zip(addrs, keccak256_many(addrs)))
    return (ref_state.state_root(dict(zip(addrs, accounts)), keys),
            ref_state.derive_sha([ref_state.receipt_rlp(1, g)
                                  for g in gas]))


def recovered(rows: list) -> list:
    """In a worker: ``secp.recover`` of each ``(hash, signature)``."""
    return [secp.recover(h, s) if s else None for h, s in rows]


def frame_senders(frames: list) -> list:
    """In a worker: the reference's sender of each gossip frame."""
    return [ref_senders.frame_sender(f) for f in frames]


def sent_by_height(transport: Transport) -> tuple:
    """What the node gossiped, off the transport's bytes: ``(requests by
    height, each a list with the retries, confirms by height)``."""
    requests: dict = {}
    confirms: dict = {}
    for _t, data in transport.gossiped:
        if _code(data) == VALIDATE_REQ:
            requests.setdefault(gen_heights.request_block(data)[0],
                                []).append(data)
        elif _code(data) == CONFIRM_BLOCK:
            confirms[int.from_bytes(ref_senders.read(data)[1][0],
                                    "big")] = data
    return requests, confirms


def judge_heights(feed, tally: Tally, requests: dict, confirms: dict,
                  d: dict, ex, rng) -> dict:
    """After the window, by the plain reference alone: every sealed
    height's request and confirm from their bytes, its block executed on
    the reference's parent state, its certificate signature by signature
    (each held to the reply the generator's reference signer made, a
    sample through ``secp.recover``), its election's supporters."""
    out = {k: 0 for k in (
        "heights_out_of_order", "sealed_not_the_head",
        "requests_not_the_sealed_block", "blocks_not_full",
        "unsound_txns_in_blocks", "txns_in_two_blocks",
        "blocks_not_executable", "commitments_wrong", "roots_compared",
        "forged_supporters", "supporters_under_threshold",
        "certificates_malformed", "elections_under_threshold",
        "reference_signatures_wrong", "reference_signatures")}
    per_blk = d["txn_per_block"]
    sealed = [(n, h) for n, h, _head, _t in tally.inserted]
    out["heights_out_of_order"] = sum(
        1 for i, (n, _h) in enumerate(sealed) if n != i + 1)
    out["sealed_not_the_head"] = sum(
        1 for _n, _h, head, _t in tally.inserted if not head)
    read = {n: ex.submit(read_request, requests[n][-1])
            for n, _h in sealed if requests.get(n)}
    state = {a: [0, feed.balance] for a in feed.addrs}
    seen: set = set()
    roots, sig_rows, sig_want = [], [], []
    executable = True
    for i, (n, bhash) in enumerate(sealed):
        p = n - 1
        req = read[n].result() if n in read else None
        if req is None or (req["code"], req["height"], req["author"],
                           req["hash"], req["version"]) != (
                VALIDATE_REQ, n, feed.node_addr, bhash, 0):
            out["requests_not_the_sealed_block"] += 1
            executable = False
            continue
        sig_rows.append((ref_state.keccak256(
            b"geec/validate-req" + rlp.encode(
                [n, feed.node_addr, bhash, 0])), req["sig"]))
        sig_want.append(feed.node_addr)
        # (2) what the block carries
        ks = [feed.index_of.get(h) for h in req["tx_hashes"]]
        out["blocks_not_full"] += len(ks) != per_blk
        out["unsound_txns_in_blocks"] += sum(
            1 for k in ks if k is None or feed.kind[k] is not None)
        out["txns_in_two_blocks"] += sum(1 for k in ks if k in seen) \
            + len(ks) - len(set(ks))
        seen.update(ks)
        for f, k in zip(req["first"], ks):  # two a height's senders
            sig_rows.append(ref_senders.row_parts(ref_senders.read(f)))
            sig_want.append(None if k is None else feed.signer(k))
        # (1) the block on the reference's parent state
        if executable and None not in ks:
            try:
                _touched, gas = ref_state.apply_transfers(state, [
                    (feed.signer(k), nonce, to, value, gas_limit)
                    for k, (nonce, to, value, gas_limit)
                    in zip(ks, req["transfers"])])
            except ref_state.Refused:
                executable = False
        else:
            executable = False
        if not executable:
            out["blocks_not_executable"] += 1
        else:
            out["commitments_wrong"] += (
                req["tx_hash"] != req["tx_root"]
                or req["gas_used"] != (gas[-1] if gas else 0)
                or req["bloom"] != ref_state.NO_BLOOM
                or req["coinbase"] != feed.node_addr
                or req["trust_rand"] != feed.seeds[n + 1])
            if i % d["roots_every"] == 0 or i == len(sealed) - 1:
                roots.append((req, ex.submit(
                    state_roots, feed.addrs,
                    [tuple(state[a]) for a in feed.addrs], gas)))
        # (4) the certificate, from the confirm's bytes
        try:
            code, c = ref_senders.read(confirms[n])
            sups, sigs = c[3], c[7]
            ok = (int.from_bytes(code, "big") == CONFIRM_BLOCK
                  and int.from_bytes(c[0], "big") == n and c[1] == bhash
                  and len(sups) == len(sigs) == len(set(sups)))
        except (KeyError, IndexError, ValueError, TypeError):
            ok = False
        if not ok:
            out["certificates_malformed"] += 1
            continue
        mine = {a: (kind, dg[-65:]) for dg, (a, kind, _s) in zip(
            feed.acks(p, bhash), feed.ack_plan[p])}
        good = [a for a, s in zip(sups, sigs)
                if mine.get(a, ("none", b""))[0] is None and mine[a][1] == s]
        out["forged_supporters"] += len(sups) - len(good)
        out["supporters_under_threshold"] += max(0, feed.need - len(sups))
        for a in rng.sample(good, min(len(good), d["cert_reference_rows"])):
            sig_rows.append((ref_quorum.ack_sighash(n, a, 1, bhash),
                             mine[a][1]))
            sig_want.append(a)
        sig_rows.append((ref_state.keccak256(b"geec/confirm" + rlp.encode(
            [n, bhash, int.from_bytes(c[2], "big"), sups, 0, 0])), c[5]))
        sig_want.append(feed.node_addr)
        # an election is won on the threshold of valid votes, no other
        handed, supporters = tally.elected.get(n, (0, frozenset()))
        valid = {a for _dg, kind, a in feed.votes[p][:handed]
                 if kind is None}
        out["elections_under_threshold"] += (
            len(supporters & valid) < feed.vote_threshold
            or bool(supporters - valid))
    step = max(1, len(sig_rows) // 16)
    got = [ex.submit(recovered, sig_rows[i:i + step])
           for i in range(0, len(sig_rows), step)]
    got = [a for f in got for a in f.result()]
    out["reference_signatures"] = len(got)
    out["reference_signatures_wrong"] = sum(
        1 for a, want in zip(got, sig_want) if a != want)
    for req, f in roots:
        root, receipts = f.result()
        out["roots_compared"] += 1
        out["commitments_wrong"] += (req["root"] != root
                                     or req["receipt_hash"] != receipts)
    out["state"] = state if executable else None
    out["sealed_frames"] = seen
    return out


def run(cell: harness.Cell, args, t0: float) -> int:
    try:
        from eges_tpu.crypto import verify_path
        from eges_tpu.utils import tracing
        if "consensus.build_proposal" not in tracing.SPANS:
            raise ImportError("no span consensus.build_proposal")
    except ImportError as e:
        print(f"this program has no measured proposer path ({e}): the cell "
              f"{cell.name} cannot run on it", file=sys.stderr)
        return 2
    d = cell.config["deployment"]
    tr = cell.traffic
    rehearse = args.rehearse
    if args.control not in (None,) + control_propose.NAMES:
        raise SystemExit(f"no control {args.control!r} for this driver")

    # -- the chip, or no run ------------------------------------------------
    device = {"platform": "none", "kind": "host C++ verifier", "count": 0,
              "memory_peak_bytes": 0}
    devs, compiles, annotate = [], None, _no_span
    if rehearse != "native":
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        if not rehearse and (device["platform"] != "tpu"
                             or len(devs) < cell.chips):
            print(f"this cell needs {cell.chips} TPU chip(s); jax found "
                  f"{device}", file=sys.stderr)
            return 3
        compiles = Compiles()
        annotate = jax.profiler.TraceAnnotation

    # -- the verify path warms while the traffic is made from the seed -----
    path = verify_path.build("native" if rehearse == "native" else "jax",
                             max_batch=d["max_batch"])
    sched = path.verifier
    warmer = threading.Thread(target=verify_path.warm, args=(path,))
    warmer.start()
    # the transfers are signed, and after the window the reference's tries
    # built, in processes of their own (spawned: this one holds the chip),
    # beside this one's cores for the warm-up
    workers = 0 if rehearse else max(1, min(8, (os.cpu_count() or 2) - 2))
    ex = concurrent.futures.ProcessPoolExecutor(
        workers, mp_context=multiprocessing.get_context("spawn")) \
        if workers else concurrent.futures.ThreadPoolExecutor(1)
    feed = gen_heights.HeightsFeed(
        args.seed, d, executor=ex,
        first_unexecutable=control_propose.FIRST_UNEXECUTABLE[args.control])
    warmer.join()

    # -- the node -----------------------------------------------------------
    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts when height ``warm`` + 1
    tally = Tally(feed)       # does
    transport = Transport()
    from eges_tpu.core.chain import BlockChain

    chain = BlockChain(verifier=sched,
                       alloc={a: feed.balance for a in feed.addrs})
    node = build_node(feed, d, chain, sched, transport,
                      control_propose.node_class(args.control))
    node.quorum = control_propose.quorum_of(args.control, node)
    tally.node = node
    node.journal.on_record = tally.on_event
    chain.add_listener(tally.on_block)
    pool = control_propose.pool_class(args.control)(
        node.clock, verifier=sched, on_admitted=tally.on_admitted)
    node.txpool = pool
    run_ = Heights(feed, node, pool, chain, transport, tally, annotate)
    run_.warm = warm
    first = [k for p in (warm, warm + 1) if p < feed.heights
             for w in feed.windows(p) for k in w]
    odd = [k for k in first if feed.kind[k] is not None]
    sample = set(odd[:n_ref // 4]) | set(rng.sample(
        first, min(len(first), n_ref - n_ref // 4)))

    threads = [threading.Thread(target=run_.feeder),
               threading.Thread(target=run_.block_path)]
    for t in threads:
        t.start()
    node.start()  # height 1's election is the node's own first step
    with run_.cv:  # warm every path the window drives
        while run_.done < warm and not run_.closed.is_set():
            run_.cv.wait(0.25)

    # -- the measured window ---------------------------------------------------
    seconds = args.seconds
    before = _snapshot(sched, pool)
    compiles_before = compiles.count if compiles else 0
    out_before = run_.outcomes()
    sealed_before = len(tally.inserted)
    pauses = GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds
    run_.go.set()

    # the traced part of the window: its last seconds
    trace_dir, trace_from, trace_rows0 = None, None, None
    if args.trace and devs:
        import jax

        trace_s = min(tr["trace_seconds"], seconds)
        run_.closed.wait(max(0.0, t_end - trace_s - time.monotonic()))
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_rows0 = _snapshot(sched, pool)
        trace_from = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # the window closes with the height that is in hand when its seconds
    # are up (whole heights over the time they took, as the acceptor's
    # cell counts them); a run that reaches the end of its stream closes
    # there
    run_.closed.wait(max(0.0, t_end - time.monotonic()))
    run_.closing.set()
    run_.closed.wait(WAIT_S)
    t_close = time.monotonic()
    after = _snapshot(sched, pool)
    out_after = run_.outcomes()
    sealed_in = len(tally.inserted) - sealed_before
    compiles_in = (compiles.count if compiles else 0) - compiles_before
    flights = sched.flights()
    lat = pauses.close(t_begin, t_close)
    exhausted = run_.exhausted.is_set()
    with run_.cv:
        run_.stop.set()
        run_.cv.notify_all()
    if trace_dir:
        traced_s = time.monotonic() - trace_from
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    node.stop()
    # the pool's window timer (5 ms) flushes what the last window left
    handed = sum(len(idx) for _b, idx in tally.handed) + tally.step_rows
    deadline = time.monotonic() + 5.0
    while run_.outcomes() < handed and time.monotonic() < deadline:
        time.sleep(0.01)
    window_s = t_close - t_begin

    # -- what the window measured --------------------------------------------
    rows_back = out_after - out_before
    end_to_end = {"verify_rows_per_s": rows_back / window_s,
                  "setup_s": setup_s}
    if devs:
        peak = max(((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for dv in devs), default=0)
        device["memory_peak_bytes"] = int(peak)
    final = _snapshot(sched, pool)
    sched.close()

    obs = {"before": before, "after": after, "window_s": window_s,
           "samples": lat, "flights": flights,
           "t_begin": t_begin, "t_end": t_close, "trace": None,
           "journal": node.journal.events()}
    breakdown = None
    if trace_dir:
        from perfbench import trace as tracemod

        red = tracemod.reduce(tracemod.load(trace_dir), traced_s,
                              program=tr["recover_program"])
        if red:
            obs["trace"] = red
            obs["trace_rows"] = (harness.pick(after, "verifier.rows") or 0) \
                - (harness.pick(trace_rows0, "verifier.rows") or 0)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            rate = peaks.achieved(red, obs["trace_rows"], device["kind"])
            if rate:
                print(json.dumps({"recover_program_u32_mac_per_s": rate,
                                  "note": "nominal textbook work over "
                                  "traced device time; no ceiling yet"}))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- what the node sent, by height, from the transport's bytes ---------
    requests, confirms = sent_by_height(transport)
    request_max = max((len(r) for rs in requests.values() for r in rs),
                      default=0)
    frames_ref = ex.submit(frame_senders,
                           [feed.frames[k] for k in sorted(sample)])
    blocks = judge_heights(feed, tally, requests, confirms, d, ex, rng)

    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    in_window = [t for _n, _h, _head, t in tally.inserted
                 if t_begin <= t <= t_close]
    per_5s = [0] * (int(window_s / 5.0) + 1)
    for t in in_window:
        per_5s[min(int((t - t_begin) / 5.0), len(per_5s) - 1)] += 1
    print("info " + json.dumps({
        "heights_per_5s": per_5s,  # how steady the window was inside
        "heights_sealed": sealed_in,
        "heights_per_s": sealed_in / window_s,
        "failed_step": run_.failed,
        # the block path's own share: the request's header read, the
        # 1023 replies signed
        "driver_ms_per_height": 1e3 * run_.driver_s / max(
            len(tally.inserted), 1),
        "request_bytes": sorted({len(v[-1]) for v in requests.values()})[-3:],
        "elected_at": sorted({v[0] for v in tally.elected.values()}),
        "certified_at": sorted({v for v in tally.certified.values()})[-3:],
        "gossiped": len(transport.gossiped),
        "pool_pending": sum(len(v) for v in pool.pending.values()),
        "scheduler_rows": harness.delta(obs, "scheduler.cache_hits")
        + harness.delta(obs, "scheduler.cache_misses"),
        "cache_hits": harness.delta(obs, "scheduler.cache_hits"),
        "device_rows": dev_rows, "host_rows": host_rows,
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "gc_full_max_ms": max(lat["gc_full_ms"], default=None),
        "unlisted": _unlisted(obs),
        "verify_rows_per_s": end_to_end["verify_rows_per_s"]}),
        file=sys.stderr)

    # -- correct: the heights, the state, then the gossip path --------------
    checks = harness.Checks()
    for name in ("blocks_not_executable", "commitments_wrong",
                 "blocks_not_full", "unsound_txns_in_blocks",
                 "txns_in_two_blocks", "requests_not_the_sealed_block",
                 "forged_supporters", "supporters_under_threshold",
                 "certificates_malformed", "elections_under_threshold",
                 "reference_signatures_wrong", "heights_out_of_order",
                 "sealed_not_the_head"):
        checks.at_most(name, blocks[name], 0)
    checks.at_most("request_bytes_max", request_max, d["request_max_bytes"])
    checks.at_least("roots_compared", blocks["roots_compared"],
                    1 + (len(tally.inserted) - 1) // d["roots_every"])
    # every account's nonce and balance after the last sealed height
    want, got = blocks["state"] or {}, chain.head_state()
    checks.at_most("accounts_wrong", sum(
        1 for a, (n, b) in want.items()
        if (got.nonce(a), got.balance(a)) != (n, b)), 0)
    checks.at_least("accounts_compared", len(want), d["accounts"])
    left = {t.hash for by in pool.pending.values() for t in by.values()}
    checks.at_most("sealed_txns_left_in_pool", sum(
        1 for k in blocks["sealed_frames"]
        if k is not None and feed.hashes[k] in left), 0)
    checks.at_least("heights_sealed", sealed_in, d["heights_sealed_min"])
    checks.at_most("steps_that_never_came", int(run_.failed is not None), 0)
    if not rehearse:  # a rehearsal's stream is a dozen heights long
        checks.equals("stream_exhausted", exhausted, False)
    verdict = tally.judge(sample)
    sent, st = verdict["sent"], final["txpool"]
    # every frame and every row of a vote, a proposal or an ACK handed
    # over since the start has an outcome
    checks.at_most("unanswered_rows", handed - run_.outcomes(), 0)
    checks.at_most("wrong_answers", verdict["wrong"], 0)
    checks.at_most("valid_frames_refused", max(0, sent["admit"]
                   + sent["admit_other"] - verdict["admitted"]["admit"]
                   - verdict["admitted"]["admit_other"]), 0)
    checks.at_most("invalid_frames_not_refused",
                   max(0, sent["reject"] - st["rejected"]), 0)
    ref_bad = sum(1 for k, want_s in zip(sorted(sample), frames_ref.result())
                  if verdict["frames"].get(k) != want_s)
    checks.at_most("reference_mismatches", ref_bad, 0)
    checks.at_least("reference_rows", len(sample), n_ref // 2)
    checks.at_least("reference_signatures", blocks["reference_signatures"],
                    len(tally.inserted))
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window", compiles_in, 0)
    ex.shutdown()

    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=rows_back,
                          failed=verdict["wrong"]
                          + blocks["reference_signatures_wrong"],
                          breakdown=breakdown, rehearse=bool(rehearse))
