"""What ONE acceptor of the 1024-validator chain receives, height after
height, when the blocks it votes on have to EXECUTE
(``drivers/acceptor.py``): the block's transactions as gossip, then the
proposer's validate request with the whole block in it, then the confirm
with its certificate.  Everything is made from ``--seed`` with the plain
reference's own keys, signatures, hashes, RLP, membership windows and state
transition (``perfbench/ref/``); every header's roots are the REFERENCE's
(``ref/state.py``), so a program whose execution or hashing differs
refuses sound blocks.

A height ``h`` (block ``p = h - 1`` of the chain):

* ``txn_per_block`` (4000) DISTINCT transfers of ``value_wei`` (1) at gas
  price 0 with ``payload_bytes`` (100) of call data: senders drawn
  uniformly from the ``senders`` (2048) sending accounts, recipients from
  all ``accounts`` (16,384, every one funded at genesis), each sender's
  nonces in order, the body in the order drawn;
* its gossip stream as ``gen_zipf`` builds one: ``txn_per_block /
  (1 - duplicate_share)`` frames (5333) in windows of ``gossip_window``:
  its own transactions that gossip brings in time (3600), the block
  before's ``unseen_share`` late ones (400), and 1333 copies of frames
  that came earlier in the stream, never in the window of their original;
  one gossip frame in ``invalid_every`` (83 of the copies) comes spoiled,
  the four kinds of ``gen.KINDS`` in turn (the chain's first block has no
  block before it and 400 frames fewer);
* the proposer is a member of the height's committee and the certificate's
  ``cert_supporters`` (513) signers are acceptors of it, by the windows of
  ``ref/membership.py`` over the seed the block before carries
  (``trust_rand``); the request is signed by the proposer, each supporter
  signs its ACK of the block's hash, the proposer signs the confirm;
* one height in ``bad_block_every``, four kinds in turn
  (:data:`BAD_KINDS`), each followed by the sound block of the same height
  as the next proposer (a member of the committee of version 1) sends it:
  ``state_root`` a header whose state root is the block before's,
  ``signature`` one transaction whose signature is none (s out of range, r
  off the curve, in turn), ``nonce_gap`` one transaction, soundly signed,
  at its sender's nonce plus one, ``certificate`` a SOUND block whose
  confirm carries its hash and supporters' genuine signatures over the
  hash of ANOTHER block of the height (the next proposer's).  The first
  three must get no ACK; the fourth gets one and must not be inserted.

Every seed gives the same counts, sizes and order of windows, requests and
confirms; the seed moves the keys, the payloads, who sends to whom, who
proposes, who certifies and which rows are late, copied and spoiled.
"""

from __future__ import annotations

import random

from perfbench.gen import KINDS, _frame, _key_base, _sign_bodies, _spoil
from perfbench.ref import membership as ref_members
from perfbench.ref import rlp, secp
from perfbench.ref import state as ref_state
from perfbench.ref.keccak import keccak256_many

BAD_KINDS = ("state_root", "signature", "nonce_gap", "certificate")
SIG_KINDS = ("s_out_of_range", "r_off_curve")
VALIDATE_REQ, CONFIRM_BLOCK = 0x11, 0x15  # gossip-plane codes
DIFFICULTY = 100                          # what a Geec proposer writes
CONFIDENCE_STEP, CONFIDENCE_CAP = 1000, 10000


def rlp_list(*encoded) -> bytes:
    """The RLP list of items that are encoded already."""
    body = b"".join(encoded)
    return rlp.length_prefix(len(body), 0xC0) + body


def transfer_body(nonce: int, gas_limit: int, to: bytes, value: int,
                  payload: bytes) -> bytes:
    """nonce, gas price 0, gas limit, to, value, payload."""
    return (rlp.encode(nonce) + b"\x80" + rlp.encode(gas_limit)
            + rlp.encode(to) + rlp.encode(value) + rlp.encode(payload))


_AT_WORK: dict = {}  # a root worker's own: the addresses and their keys


def _at_work(addrs: list) -> None:
    _AT_WORK["addrs"] = addrs
    _AT_WORK["keys"] = dict(zip(addrs, keccak256_many(addrs)))


def _block_roots(frames: list, accounts: list) -> tuple:
    """In a worker: a block's transaction root and the state root after
    it, both tries built whole (``accounts`` in the addresses' order)."""
    return (ref_state.derive_sha(frames), ref_state.state_root(
        dict(zip(_AT_WORK["addrs"], accounts)), _AT_WORK["keys"]))


def _root_workers(n: int, addrs: list):
    """``n`` fresh interpreters (spawned: the caller may hold a chip, and
    a forked copy of it would too) that import the reference and nothing
    of the program."""
    import concurrent.futures
    import multiprocessing

    return concurrent.futures.ProcessPoolExecutor(
        n, mp_context=multiprocessing.get_context("spawn"),
        initializer=_at_work, initargs=(addrs,))


class Step:
    """One message of a height, as bytes for ``GeecNode.on_gossip``."""

    def __init__(self, what: str, data: bytes, block_hash: bytes,
                 rows: int, *, sound: bool = True, bad: str | None = None):
        self.what = what              # "request" or "confirm"
        self.data = data
        self.block_hash = block_hash
        self.rows = rows              # signature rows the handler asks
        self.sound = sound            # request: must be ACKed; confirm:
        #                               must insert its block
        self.bad = bad                # the kind of bad block, where one


class ChainFeed:
    def __init__(self, seed: int, d: dict, first_bad: str = BAD_KINDS[0],
                 workers: int = 0):
        rng = random.Random(seed)
        self.d = d
        n_acc, n_send = d["accounts"], d["senders"]
        per_blk, n_blk = d["txn_per_block"], d["chain_blocks"]
        win, every = d["gossip_window"], d["bad_block_every"]
        self.late = int(per_blk * d["unseen_share"])
        self.gossip_frames = round(per_blk / (1.0 - d["duplicate_share"]))
        self.copies = self.gossip_frames - per_blk
        self.spoiled = round(self.gossip_frames / d["invalid_every"])

        # -- who is who ----------------------------------------------------
        acc_privs, self.addrs = secp.keys(_key_base(rng), n_acc)
        val_privs, val_addrs = secp.keys(_key_base(rng), d["validators"])
        self.validators = [(a, "10.%d.%d.%d" % (i >> 16, i >> 8 & 255,
                                                i & 255), 8100 + i)
                           for i, a in enumerate(val_addrs)]
        me = rng.randrange(d["validators"])
        self.node_priv, self.node_addr = val_privs[me], val_addrs[me]
        priv_of = self.priv_of = dict(zip(val_addrs, val_privs))
        at_of = {a: (ip, port) for a, ip, port in self.validators}
        members = sorted(val_addrs)
        self.need = ref_members.majority(d["acceptors"], len(members))
        self.balance = d["balance_wei"]
        self.senders = rng.sample(range(n_acc), n_send)

        # -- the transfers, block by block, and what they do to the state --
        nonce = [0] * n_acc
        self.account, bodies, transfers = [], [], []
        for _k in range(n_blk * per_blk):
            a = self.senders[rng.randrange(n_send)]
            to = self.addrs[rng.randrange(n_acc)]
            bodies.append(transfer_body(nonce[a], d["gas_limit"], to,
                                        d["value_wei"],
                                        rng.randbytes(d["payload_bytes"])))
            transfers.append((self.addrs[a], nonce[a], to, d["value_wei"],
                              d["gas_limit"]))
            self.account.append(a)
            nonce[a] += 1
        sigs = _sign_bodies(bodies, [acc_privs[a] for a in self.account],
                            rng)
        self.frames = [_frame(b, s) for b, s in zip(bodies, sigs)]
        self.n_valid = len(self.frames)
        self.kind = [None] * self.n_valid      # of every frame
        self.origin = list(range(self.n_valid))  # the sound frame under it
        meant: dict = {}  # sound frame -> signer, of spoiled SIGNATURES

        def spoil(k: int, kind: str) -> int:
            """A spoiled variant of sound frame ``k``; its index."""
            body, sig, v = bodies[k], sigs[k], None
            if kind == "bad_recid":
                v = 27 + 5
            elif kind == "flipped_message":
                body = body[:-1] + bytes([body[-1] ^ 0x40])
            else:
                meant[k] = self.addrs[self.account[k]]
            self.frames.append(_frame(body, _spoil(kind, sig, rng), v))
            self.kind.append(kind)
            self.origin.append(k)
            return len(self.frames) - 1

        # -- each block's gossip stream (gen_zipf's construction) -----------
        unseen = [set(rng.sample(range(p * per_blk, (p + 1) * per_blk),
                                 self.late)) for p in range(n_blk)]
        self.unseen = unseen
        self.blocks = []
        for p in range(n_blk):
            own = range(p * per_blk, (p + 1) * per_blk)
            fresh = [k for k in own if k not in unseen[p]] \
                + (sorted(unseen[p - 1]) if p else [])
            rng.shuffle(fresh)
            head = min(len(fresh), self.copies + win)
            marks = [False] * (len(fresh) - head) + [True] * self.copies
            rng.shuffle(marks)
            again = fresh[:self.copies]
            rng.shuffle(again)
            seq, nxt, slots = fresh[:head], head, []
            for is_copy in marks:
                if is_copy:
                    slots.append(len(seq))
                    seq.append(again.pop())
                else:
                    seq.append(fresh[nxt])
                    nxt += 1
            first = p * self.spoiled  # the four kinds in turn, all blocks
            for i, at in enumerate(rng.sample(slots, self.spoiled)):
                seq[at] = spoil(seq[at], KINDS[(first + i) % 4])
            self.blocks.append([seq[i:i + win]
                                for i in range(0, len(seq), win)])

        # -- the chain: every header's roots are the reference's ----------
        state = {a: [0, self.balance] for a in self.addrs}
        keys = dict(zip(self.addrs, keccak256_many(self.addrs)))
        trie = ref_state.SecureState(state, keys)
        genesis = {"parent_hash": bytes(32), "coinbase": bytes(20),
                   "root": trie.root(), "tx_hash": ref_state.EMPTY_ROOT,
                   "receipt_hash": ref_state.EMPTY_ROOT,
                   "bloom": ref_state.NO_BLOOM, "difficulty": 1,
                   "number": 0, "gas_limit": 0, "gas_used": 0, "time": 0,
                   "extra": b"geec-genesis", "trust_rand": 0}
        self.genesis_hash = ref_state.keccak256(
            ref_state.header_rlp(genesis))
        # the transition, block by block; the two roots that differ from
        # block to block either here, a block at a time (the secure trie
        # re-encoding what the block touched), or in ``workers``
        # processes that each build a block's two tries whole
        self.deltas: list = []       # per block: {address: (nonce, balance)}
        roots, gas_of = [], []
        pool = _root_workers(workers, self.addrs) if workers else None
        for p in range(n_blk):
            touched, gas = ref_state.apply_transfers(
                state, transfers[p * per_blk:(p + 1) * per_blk])
            self.deltas.append({a: tuple(state[a]) for a in touched})
            gas_of.append(gas)
            own = self.frames[p * per_blk:(p + 1) * per_blk]
            if pool is not None:
                roots.append(pool.submit(
                    _block_roots, own, [tuple(state[a]) for a in self.addrs]))
                continue
            for a in touched:
                trie.set(a, *state[a])
            roots.append((ref_state.derive_sha(own), trie.root()))
        if pool is not None:
            roots = [f.result() for f in roots]
            pool.shutdown()
        self.steps: list = []        # per block: the messages, in order
        self.bad: dict = {}          # block -> its kind of bad block
        self.never_insert: set = set()
        self.block_hashes: list = []  # the canonical block of each height
        self.headers: list = []      # its header's fields, but the author
        receipt_roots: dict = {}     # by count: transfers' receipts are alike
        first_at = BAD_KINDS.index(first_bad)
        sign_jobs: list = []         # (priv, message to hash) -> signature
        parent_hash, parent_root, seed_h = self.genesis_hash, \
            genesis["root"], 0
        confidence = 0
        pending: list = []           # closures that need the signatures

        def sign(priv: int, message: bytes) -> int:
            sign_jobs.append((priv, message))
            return len(sign_jobs) - 1

        for p in range(n_blk):
            h = p + 1
            rows = list(range(p * per_blk, (p + 1) * per_blk))
            gas = gas_of[p]
            tx_root, root = roots[p]
            if per_blk not in receipt_roots:
                receipt_roots[per_blk] = ref_state.derive_sha(
                    [ref_state.receipt_rlp(1, g) for g in gas])
            txs = rlp_list(*self.frames[p * per_blk:(p + 1) * per_blk])
            trust_rand = rng.getrandbits(64)
            sound = {"parent_hash": parent_hash, "root": root,
                     "tx_hash": tx_root,
                     "receipt_hash": receipt_roots[per_blk],
                     "bloom": ref_state.NO_BLOOM, "difficulty": DIFFICULTY,
                     "number": h, "gas_limit": 0, "gas_used": gas[-1],
                     "time": h, "extra": b"", "trust_rand": trust_rand}
            others = [a for a in members if a != self.node_addr]
            # this height's proposer and, of the version-1 committee,
            # the next one: another validator, so another block
            proposers: list = []
            for v in (0, 1):
                proposers.append(rng.choice([
                    a for a in ref_members.committee(
                        members, seed_h, v, d["committee"])
                    if a != self.node_addr and a not in proposers]))
            confidence = min(confidence + CONFIDENCE_STEP, CONFIDENCE_CAP)
            bad = None
            if h % every == every // 2:
                bad = BAD_KINDS[(first_at + h // every) % len(BAD_KINDS)]
                self.bad[p] = bad

            def block_of(header: dict, txs_enc: bytes, author: bytes):
                enc = ref_state.header_rlp({**header, "coinbase": author})
                return (ref_state.keccak256(enc),
                        rlp_list(enc, b"\xc0", b"\xc0", txs_enc, b"\xc0",
                                 b"\xc0"))

            def request(bhash, block_enc, author, version, n_rows, **kw):
                job = sign(priv_of[author], b"geec/validate-req" + rlp.encode(
                    [h, author, bhash, version]))
                ip, port = at_of[author]
                step = Step("request", b"", bhash, n_rows + 1, **kw)

                def finish(sig_of, h=h):
                    step.data = rlp_list(rlp.encode(VALIDATE_REQ), rlp_list(
                        rlp.encode(h), rlp.encode(author), block_enc,
                        rlp.encode(ip.encode()), rlp.encode(port),
                        b"\x80", rlp.encode(version), b"\xc0",
                        rlp.encode(sig_of[job])))
                pending.append(finish)
                return step

            def confirm(bhash, author, signed_hash, conf, **kw):
                sups = rng.sample(others, self.need)
                jobs = [sign(priv_of[a], b"geec/ack" + rlp.encode(
                    [h, a, 1, signed_hash])) for a in sups]
                mine = sign(priv_of[author], b"geec/confirm" + rlp.encode(
                    [h, bhash, conf, sups, 0, 0]))
                step = Step("confirm", b"", bhash, self.need + 1, **kw)

                def finish(sig_of, h=h):
                    step.data = rlp.encode([CONFIRM_BLOCK, [
                        h, bhash, conf, sups, 0, sig_of[mine], 0,
                        [sig_of[j] for j in jobs]]])
                pending.append(finish)
                return step

            steps: list = []
            if bad is None:
                bhash, enc = block_of(sound, txs, proposers[0])
                steps = [request(bhash, enc, proposers[0], 0, per_blk),
                         confirm(bhash, proposers[0], bhash, confidence)]
            else:
                twin_hash, twin_enc = block_of(sound, txs, proposers[1])
                header, bad_txs = sound, txs
                if bad == "state_root":
                    header = {**sound, "root": parent_root}
                elif bad in ("signature", "nonce_gap"):
                    at = rng.randrange(per_blk)
                    k = rows[at]
                    if bad == "signature":
                        rows[at] = spoil(k, SIG_KINDS[
                            (h // every // len(BAD_KINDS)) % 2])
                    else:
                        a = self.account[k]
                        body = transfer_body(
                            transfers[k][1] + 1, d["gas_limit"],
                            transfers[k][2], d["value_wei"],
                            rng.randbytes(d["payload_bytes"]))
                        self.frames.append(_frame(body, _sign_bodies(
                            [body], [acc_privs[a]], rng)[0]))
                        self.kind.append("nonce_gap")
                        self.origin.append(k)
                        rows[at] = len(self.frames) - 1
                    enc = [self.frames[k] for k in rows]
                    bad_txs = rlp_list(*enc)
                    header = {**sound, "tx_hash": ref_state.derive_sha(enc)}
                bhash, enc = block_of(header, bad_txs, proposers[0])
                self.never_insert.add(bhash)
                if bad == "certificate":
                    steps = [request(bhash, enc, proposers[0], 0, per_blk,
                                     bad=bad),
                             confirm(bhash, proposers[0], twin_hash,
                                     confidence, sound=False, bad=bad)]
                else:
                    steps = [request(bhash, enc, proposers[0], 0, per_blk,
                                     sound=False, bad=bad)]
                steps += [request(twin_hash, twin_enc, proposers[1], 1,
                                  per_blk),
                          confirm(twin_hash, proposers[1], twin_hash,
                                  confidence)]
                bhash = twin_hash
            self.steps.append(steps)
            self.block_hashes.append(bhash)
            self.headers.append(sound)
            parent_hash, parent_root, seed_h = bhash, root, trust_rand

        hashes = keccak256_many(m for _priv, m in sign_jobs)
        sig_of = secp.sign_rows([priv for priv, _m in sign_jobs], hashes,
                                _key_base(rng))
        for finish in pending:
            finish(sig_of)
        self.hashes = keccak256_many(self.frames)
        self.index_of = {h: k for k, h in enumerate(self.hashes)}
        # the accept_all control answers a spoiled signature with the
        # sender the generator meant: by the row's signing hash
        self.meant = dict(zip(keccak256_many(
            rlp.length_prefix(len(bodies[k]), 0xC0) + bodies[k]
            for k in meant), meant.values()))

    # what the run asks for ---------------------------------------------
    def windows(self, block: int) -> list:
        """Block ``block``'s gossip windows, each a list of frame indices."""
        return self.blocks[block]

    def signer(self, k: int) -> bytes:
        """The account that signed the sound frame under frame ``k``."""
        return self.addrs[self.account[self.origin[k]]]

    def frame_expect(self, k: int):
        """What the pool must do with a fresh frame k: ``("admit",
        sender)``, ``("admit_other", signer)`` where the message was
        altered after signing, or ``("reject", None)``."""
        kind = self.kind[k]
        if kind in (None, "nonce_gap"):
            return "admit", self.signer(k)
        if kind == "flipped_message":
            return "admit_other", self.signer(k)
        return "reject", None

    def state_at(self, height: int) -> dict:
        """The reference's state after block ``height``: address ->
        ``(nonce, balance)``, every account."""
        out = {a: (0, self.balance) for a in self.addrs}
        for delta in self.deltas[:height]:
            out.update(delta)
        return out

    def construction(self, block: int) -> dict:
        """A block's rows by construction: gossip frames by what becomes
        of them, the rows each message's handler asks of the verifier, and
        the accounts the block touches."""
        seq = [k for w in self.blocks[block] for k in w]
        spoiled = [k for k in seq if self.kind[k] is not None]
        per_blk = self.d["txn_per_block"]
        own = range(block * per_blk, (block + 1) * per_blk)
        return {"gossip_frames": len(seq),
                "own_in_time": sum(1 for k in set(seq) if k in own),
                "late_of_previous": sum(
                    1 for k in set(seq) if self.kind[k] is None
                    and k not in own),
                "copies": len(seq) - len(set(seq)),
                "spoiled": len(spoiled),
                "unseen_at_request": len(self.unseen[block]),
                "touched_accounts": len(self.deltas[block]),
                "bad": self.bad.get(block),
                "steps": [(s.what, s.rows, s.sound) for s in
                          self.steps[block]],
                # a sound height: gossip + body + request + certificate
                # and the confirm's own signature
                "rows_asked": len(seq) + sum(s.rows for s in
                                             self.steps[block])}
