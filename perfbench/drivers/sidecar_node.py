"""One node process of a host whose verify path is a sidecar: what
``drivers/sidecar.py`` starts three of.

The process builds what ``eges_tpu/node/service.py`` builds for
``--verifier sidecar`` (``crypto/verify_path.build("sidecar", ...)``: a
``SidecarClient`` in the scheduler's place, no jax), a ``TxPool`` on it,
and runs the node driver's own ``Node`` and ``Tally`` (``drivers/node.py``,
imported, not copied) over its share of the chain
(``gen_shared.SharedFeed``).  It is told what to do on its standard input
and answers on its standard output, one JSON object a line behind the
mark ``@@``, a line a BLOCK or a snapshot, never a row::

    driver -> node   {"op": "block", "b": 7}       run block 7
                     {"op": "snap", "tag": "..."}  read the counters now
                     {"op": "finish"}              judge, answer, exit
    node -> driver   {"ev": "ready"}    feed, pool and client are built
                     {"ev": "done", "b": 7}
                     {"ev": "snap", "tag": "...", "data": {...}}
                     {"ev": "result", "data": {...}}

A snapshot is the process's registry (span histograms and counters), the
pool's stats, the client's stats and the rows answered so far, stamped
with ``time.monotonic()``, which every process of one host reads off the
same clock.
"""

from __future__ import annotations

import json
import queue
import random
import sys
import threading
import time

from perfbench import gen_shared, harness
from perfbench.clock import ThreadClock
from perfbench.drivers import node as one

MARK = "@@ "


class NodeRunner:
    """The verify path of one co-hosted node on ``verifier``, the calls
    that feed it, and its own judgment of every answer it got."""

    def __init__(self, cell: harness.Cell, seed: int, index: int, verifier,
                 inner=None):
        from eges_tpu.core.txpool import TxPool

        d = cell.config["deployment"]
        self.d, self.index, self.verifier = d, index, verifier
        feed = self.feed = gen_shared.SharedFeed(seed, index, d, inner)
        # the rows that go through the plain reference afterwards: the
        # node driver's choice, from this node's own first two blocks
        rng = random.Random(seed ^ 0x5A17 ^ (index << 20))
        n_ref, warm = d["reference_rows"], cell.traffic["warm_blocks"]
        first = [k for w in feed.windows(warm) + feed.windows(warm + 1)
                 for k in w]
        odd = [k for k in first if feed.frame_kind[k] is not None]
        self.sample = set(odd[:n_ref // 4]) | set(
            rng.sample(first, n_ref // 2))
        votes0 = [i for b in range(warm, warm + feed.nodes)
                  for part in feed.votes(b) for i in part]
        vodd = [i for i in votes0 if feed.vote_kind[i] is not None]
        self.vote_sample = set(vodd[:n_ref // 8]) | set(
            rng.sample(votes0, min(len(votes0), n_ref // 8)))
        self.tally = one.Tally(feed)
        self.pool = TxPool(ThreadClock(), verifier=verifier,
                           on_admitted=self.tally.on_admitted)
        self.node = one.Node(feed, verifier, self.pool, self.tally,
                             one._no_span)
        self.pauses = one.GcPauses()
        self.t_built = time.monotonic()

    def whole_block(self, b: int) -> None:
        for idx in self.feed.windows(b):
            self.node.window(b, idx)
        for rows in self.feed.votes(b):
            if len(rows):
                self.tally.votes(rows, self.node.vote_batch(rows))
        self.node.commit()

    def snapshot(self) -> dict:
        from eges_tpu.utils.metrics import DEFAULT as metrics

        return {"t": time.monotonic(), "outcomes": self.node.outcomes(),
                "registry": metrics.snapshot(),
                "txpool": dict(self.pool.stats),
                "client": self.verifier.stats()}

    def finish(self) -> dict:
        """After the last block: every row's outcome against what the
        generator knows, the sample through the plain reference."""
        tally, node = self.tally, self.node
        # the pool's window timer (5 ms) flushes what the last block left
        handed = sum(len(idx) for _b, idx in tally.handed) + sum(
            len(a) for _r, a in tally.vote_log)
        deadline = time.monotonic() + 5.0
        while node.outcomes() < handed and time.monotonic() < deadline:
            time.sleep(0.01)
        verdict = tally.judge(self.sample, self.vote_sample)
        ref_rows, ref_bad = one._reference(self.feed, verdict, self.sample)
        sent, st = verdict["sent"], dict(self.pool.stats)
        gc_ = self.pauses.close(self.t_built, time.monotonic())
        return {
            "node": self.index, "handed": handed,
            "unanswered_rows": handed - node.outcomes(),
            "wrong_answers": verdict["wrong"],
            "valid_frames_refused": max(
                0, sent["admit"] + sent["admit_other"]
                - verdict["admitted"]["admit"]
                - verdict["admitted"]["admit_other"]),
            "invalid_frames_not_refused": max(
                0, sent["reject"] - st["rejected"]),
            "reference_rows": ref_rows, "reference_mismatches": ref_bad,
            "sent": sent, "txpool": st,
            "client": self.verifier.stats(),
            "gc_ms": sum(gc_["gc_ms"]), "gc_full": len(gc_["gc_full_ms"]),
            "jax_imported": "jax" in sys.modules}


class NodeLoop:
    """``workers`` threads that run the blocks a node is told to run and
    say when each is done."""

    def __init__(self, runner: NodeRunner, workers: int, on_done):
        self.runner, self.on_done = runner, on_done
        self._q: queue.Queue = queue.Queue(64)
        self.failed: list = []
        self._threads = [threading.Thread(target=self._work, daemon=True)
                         for _ in range(workers)]
        for t in self._threads:
            t.start()

    def _work(self) -> None:
        while True:
            b = self._q.get()
            if b is None:
                return
            try:
                self.runner.whole_block(b)
            except Exception as e:  # the driver must hear of it, not hang
                self.failed.append(f"block {b}: {e!r}")
            self.on_done(b)

    def block(self, b: int) -> None:
        self._q.put(b)

    def stop(self, timeout: float = 120.0) -> None:
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--node", type=int, required=True)
    ap.add_argument("--socket", required=True)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    out_lock = threading.Lock()

    def emit(obj: dict) -> None:
        line = MARK + json.dumps(obj, default=str) + "\n"
        with out_lock:
            sys.stdout.write(line)
            sys.stdout.flush()

    from eges_tpu.crypto import verify_path

    cell = harness.Cell(args.workload, bool(args.rehearse))
    path = verify_path.build("sidecar", sidecar_path=args.socket)
    runner = NodeRunner(cell, args.seed, args.node, path.verifier)
    loop = NodeLoop(runner, cell.traffic["blocks_in_flight"],
                    lambda b: emit({"ev": "done", "b": b}))
    emit({"ev": "ready"})
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["op"] == "block":
            loop.block(msg["b"])
        elif msg["op"] == "snap":
            emit({"ev": "snap", "tag": msg["tag"],
                  "data": runner.snapshot()})
        elif msg["op"] == "finish":
            loop.stop()
            emit({"ev": "result",
                  "data": {**runner.finish(), "failed": loop.failed}})
            break
    path.verifier.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
