"""What ONE validator of the 1024-validator chain receives, height after
height, when a block is 4000 CALLS of BLOCKBENCH's Smallbank contract
(``drivers/smallbank.py``): ``gen_chain``'s stream (the block's
transactions as gossip, the proposer's validate request with the whole
block, the confirm with its certificate) with a contract call where that
has a transfer.  Everything is made from ``--seed`` with the plain
reference's own keys, signatures, hashes, RLP, membership windows,
interpreter and tries (``perfbench/ref/``); every header's state root,
receipts root and gas used are the REFERENCE's, so a program that
executes, charges or hashes otherwise refuses sound blocks.

What differs from ``gen_chain.ChainFeed`` (whose construction of the
gossip stream, the committees, the requests and the confirms is repeated
here: that class is one constructor and no file of the benchmark that is
there may be edited; ``Step``, ``rlp_list``, ``transfer_body`` and the
constants are imported):

* **the genesis** holds, beside the ``accounts`` (16,384) funded
  accounts, the contract at ``contract_address`` with Smallbank's code
  (``ref/contracts.py``) and ``2 x customers`` storage slots: customer
  ``c``'s savings and checking balance, each drawn from ``balance_min ..
  balance_max``; its header carries ``block_gas_limit``, and so does every
  header after it;
* **a transaction** is a call of the contract at value 0 and gas price 0
  with ``call_gas_limit``: the procedure drawn by ``mix`` (OLTPBench's
  shares), each customer from the ``hot_customers`` (100) with probability
  ``hot_share`` (0.25) and uniformly from the others else, two customers
  of one call distinct; the amounts are ``amounts``'; one call in
  ``abort_every`` (64) is a ``sendPayment`` of one more than its payer
  holds, which the bytecode REVERTs after it has stored the payee's credit
  (others abort because their payer has been amalgamated to nothing:
  those are counted, not placed).  ``sendPayment``'s call data is 100 B,
  the other procedures' 36 or 68 B;
* **the transition** is run three times and held together: the six
  procedures as plain Python on two dicts while the calls are drawn
  (``ref/contracts.py Bank``: who aborts, what every slot holds after
  every block), the bytecode under ``ref/evm.py`` a block a job in worker
  processes (status and gas of every receipt; the slots it leaves must be
  the plain Python's, or set-up fails), and the two tries
  (``ref/trie.py``): the contract's storage trie, which changes shape
  where a balance becomes 0, and the accounts' trie with the contract's
  leaf over that block's storage root;
* **bad blocks** are of five kinds in turn (:data:`BAD_KINDS`):
  ``gen_chain``'s four and ``gas_used``, a header that claims one gas
  more than the calls used.

Every seed gives the same counts, sizes and order of windows, requests and
confirms; the seed moves the keys, who calls what for whom, the balances,
who proposes, who certifies and which rows are late, copied and spoiled.
"""

from __future__ import annotations

import random

from perfbench.gen import KINDS, _frame, _key_base, _sign_bodies, _spoil
from perfbench.gen_chain import (CONFIDENCE_CAP, CONFIDENCE_STEP,
                                 CONFIRM_BLOCK, DIFFICULTY, SIG_KINDS,
                                 VALIDATE_REQ, Step, rlp_list,
                                 transfer_body)
from perfbench.ref import contracts, rlp, secp
from perfbench.ref import evm as ref_evm
from perfbench.ref import membership as ref_members
from perfbench.ref import state as ref_state
from perfbench.ref import trie as ref_trie
from perfbench.ref.keccak import keccak256, keccak256_many

BAD_KINDS = ("state_root", "signature", "nonce_gap", "certificate",
             "gas_used")
TWO_CUSTOMERS = ("almagate", "sendPayment")
CODE_HASH = keccak256(contracts.SMALLBANK)


def _word(v: int) -> bytes:
    return v.to_bytes(32, "big")


def account_rlp(nonce: int, balance: int, storage_root: bytes,
                code_hash: bytes) -> bytes:
    """``[nonce, balance, storage root, code hash]``: the account RLP of
    ``ref/state.py`` for an account that holds storage and code."""
    return rlp.encode([nonce, balance, storage_root, code_hash])


# -- what the worker processes do (fresh interpreters: the reference and
#    nothing of the program) ------------------------------------------------

def _run_block(job) -> tuple:
    """A block's calls through ``ref/evm.py``: ``(statuses, cumulative
    gas, slot -> value after the block, receipts root)``.  ``known``:
    ``(customer, mapping, slot, value before the block)`` of every slot
    the block's calls name."""
    datas, known, gas_limit = job
    memo = {contracts.slot_preimage(c, m): s.to_bytes(32, "big")
            for c, m, s, _v in known}
    storage = {s: v for _c, _m, s, v in known if v}

    def keccak(data: bytes) -> bytes:
        return memo.get(data) or keccak256(data)

    statuses, cumulative, gas, written = [], [], 0, {}
    for data in datas:
        status, used, writes = ref_evm.apply_call(
            contracts.SMALLBANK, data, storage, gas_limit, keccak)
        for slot, value in writes.items():
            written[slot] = value
            if value:
                storage[slot] = value
            else:
                storage.pop(slot, None)
        gas += used
        statuses.append(status)
        cumulative.append(gas)
    return statuses, cumulative, written, ref_state.derive_sha(
        [ref_state.receipt_rlp(s, g) for s, g in zip(statuses, cumulative)])


def _part_refs(job) -> list:
    """A secure trie's part under the top nibbles ``mine``, version after
    version: ``versions[0]`` the ``(key, value)`` pairs it starts with,
    each later one the writes of a block (an empty value deletes).
    Returns, a version, what stands for each of ``mine``'s sub-tries in
    the top branch (``0x80``: none).  Every version is built first and
    all are hashed together (``ref/trie.py refer``): a Keccak alone
    costs what a thousand cost in one call, which is also why the parts
    are few."""
    mine, versions = job
    every = list({k for pairs in versions for k, _v in pairs})
    hashed = dict(zip(every, keccak256_many(every)))
    trie = ref_trie.Trie()
    for pairs in versions:
        for key, value in pairs:
            key = hashed[key]
            if key[0] >> 4 in mine:
                trie.set(key, value)
        trie.commit()
    ref_trie.refer(trie.tops)
    out = []
    for top in trie.tops:
        if top is None or top[0] != ref_trie.BRANCH:
            raise AssertionError("a part this small has no branch at its "
                                 "top: fewer parts, or more keys")
        out.append({n: b"\x80" if top[1][n] is None else top[1][n][3]
                    for n in mine})
    return out


def trie_roots(pool, versions: list, split: int) -> list:
    """The roots of a secure trie's versions (:func:`_part_refs`'s),
    its top nibbles dealt to ``split`` jobs of ``pool``; a function to
    call for the answer."""
    jobs = [pool.submit(_part_refs, (set(range(16)[i::split]), versions))
            for i in range(split)]

    def roots() -> list:
        out = []
        for at in zip(*(f.result() for f in jobs)):
            refs = {n: r for part in at for n, r in part.items()}
            body = b"".join(refs[n] for n in range(16)) + b"\x80"
            out.append(keccak256(rlp.length_prefix(len(body), 0xC0) + body))
        return out
    return roots


def _workers(n: int):
    import concurrent.futures
    import multiprocessing

    return concurrent.futures.ProcessPoolExecutor(
        n, mp_context=multiprocessing.get_context("spawn"))


class _Inline:
    """In the workers' place where there are none (a rehearsal, a test)."""

    def submit(self, fn, job):
        import concurrent.futures

        f = concurrent.futures.Future()
        f.set_result(fn(job))
        return f

    def shutdown(self):
        pass


class ContractFeed:
    def __init__(self, seed: int, d: dict, first_bad: str = BAD_KINDS[0],
                 workers: int = 0):
        rng = random.Random(seed)
        self.d = d
        n_acc, n_send = d["accounts"], d["senders"]
        per_blk, n_blk = d["txn_per_block"], d["chain_blocks"]
        win, every = d["gossip_window"], d["bad_block_every"]
        n_cust, n_hot = d["customers"], d["hot_customers"]
        self.late = int(per_blk * d["unseen_share"])
        self.gossip_frames = round(per_blk / (1.0 - d["duplicate_share"]))
        self.copies = self.gossip_frames - per_blk
        self.spoiled = round(self.gossip_frames / d["invalid_every"])
        self.contract = bytes.fromhex(d["contract_address"])
        self.code = contracts.SMALLBANK
        self.gas_limit = d["block_gas_limit"]
        pool = _workers(workers) if workers else _Inline()

        # -- who is who (gen_chain's) ---------------------------------------
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

        # -- the customers, and where the contract keeps them ----------------
        lo, hi = d["balance_min"], d["balance_max"]
        customers = range(1, n_cust + 1)
        books = ({c: rng.randint(lo, hi) for c in customers},   # SAVING
                 {c: rng.randint(lo, hi) for c in customers})   # CHECKING
        self.slot = {(m, c): s for m in (contracts.SAVING,
                                         contracts.CHECKING)
                     for c, s in zip(customers,
                                     contracts.slots_of(customers, m))}
        self.genesis_storage = {self.slot[m, c]: v for m in (0, 1)
                                for c, v in books[m].items()}
        bank = contracts.Bank(*books)

        # -- the calls, block by block, run as plain Python as drawn ---------
        names = list(d["mix"])
        shares = [d["mix"][n] for n in names]
        amounts = d["amounts"]

        def customer(other: int = 0) -> int:
            while True:
                c = 1 + rng.randrange(n_hot) if rng.random() < d["hot_share"] \
                    else 1 + n_hot + rng.randrange(n_cust - n_hot)
                if c != other:
                    return c

        nonce = [0] * n_acc
        self.account, self.calls, self.aborted = [], [], []
        bodies, datas, nonces = [], [], []
        self.slot_deltas: list = []   # per block: {(mapping, c): value}
        self.deltas: list = []        # per block: {address: (nonce, bal)}
        before: list = []             # per block: what its calls name
        for p in range(n_blk):
            named: dict = {}
            touched: set = set()
            for i in range(per_blk):
                k = p * per_blk + i
                name = rng.choices(names, shares)[0]
                args = [customer()]
                if name in TWO_CUSTOMERS:
                    args.append(customer(args[0]))
                if name in amounts:
                    args.append(amounts[name])
                if k % d["abort_every"] == d["abort_every"] - 1:
                    # one more than the payer holds (a payer who holds
                    # all that a word can say holds enough for anything)
                    name, payer = "sendPayment", args[0]
                    while books[1].get(payer, 0) == contracts.U256 - 1:
                        payer = customer()
                    args = [payer, customer(payer),
                            books[1].get(payer, 0) + 1]
                for c in args[:2 if name in TWO_CUSTOMERS else 1]:
                    for m in (contracts.SAVING, contracts.CHECKING):
                        named.setdefault((m, c), books[m].get(c, 0))
                try:
                    getattr(bank, name)(*args)
                    self.aborted.append(False)
                except contracts.Aborted:
                    self.aborted.append(True)
                a = self.senders[rng.randrange(n_send)]
                data = contracts.call_data(name, *args)
                nonces.append(nonce[a])
                bodies.append(transfer_body(nonce[a], d["call_gas_limit"],
                                            self.contract, 0, data))
                datas.append(data)
                self.calls.append((name, args))
                self.account.append(a)
                touched.add(a)
                nonce[a] += 1
            before.append(named)
            self.slot_deltas.append({
                mc: books[mc[0]].get(mc[1], 0) for mc, v in named.items()
                if books[mc[0]].get(mc[1], 0) != v})
            self.deltas.append({self.addrs[a]: (nonce[a], self.balance)
                                for a in touched})
        # -- the reference's interpreter and tries, beside this process:
        #    the storage trie first (it takes the longest), a block's
        #    calls a job, both while the frames are signed here ---------
        split = max(1, min(workers // 2, 4))
        storage_roots = trie_roots(pool, [
            [(_word(s), rlp.encode(v))
             for s, v in self.genesis_storage.items()]] + [
            [(_word(self.slot[mc]), rlp.encode(v) if v else b"")
             for mc, v in delta.items()] for delta in self.slot_deltas],
            split)
        ran = [pool.submit(_run_block, (
            datas[p * per_blk:(p + 1) * per_blk],
            [(c, m, self.slot[m, c], v) for (m, c), v in before[p].items()],
            d["call_gas_limit"])) for p in range(n_blk)]
        sigs = _sign_bodies(bodies, [acc_privs[a] for a in self.account],
                            rng)
        self.frames = [_frame(b, s) for b, s in zip(bodies, sigs)]
        self.n_valid = len(self.frames)
        tx_roots = [pool.submit(ref_state.derive_sha,
                                self.frames[p * per_blk:(p + 1) * per_blk])
                    for p in range(n_blk)]

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

        # -- each block's gossip stream (gen_chain's construction) -----------
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

        # -- the accounts' trie over each block's storage root; the three
        #    transitions held together --------------------------------------
        self.storage_roots = storage_roots()
        leaf = [account_rlp(1, 0, r, CODE_HASH) for r in self.storage_roots]
        state_roots = trie_roots(pool, [
            [(a, ref_state.account_rlp(0, self.balance))
             for a in self.addrs] + [(self.contract, leaf[0])]] + [
            [(a, ref_state.account_rlp(n, b)) for a, (n, b) in delta.items()]
            + [(self.contract, leaf[p + 1])]
            for p, delta in enumerate(self.deltas)], split)
        self.receipts: list = []       # per block: (statuses, gas)
        receipt_roots = []
        for p, f in enumerate(ran):
            statuses, gas, written, receipt_root = f.result()
            if [not s for s in statuses] \
                    != self.aborted[p * per_blk:(p + 1) * per_blk]:
                raise AssertionError(f"block {p}: the bytecode and the "
                                     "plain procedures abort otherwise")
            kept = {self.slot[mc]: v for mc, v in
                    self.slot_deltas[p].items()}
            was = {self.slot[mc]: v for mc, v in before[p].items()}
            if {s: v for s, v in written.items() if was[s] != v} != kept:
                raise AssertionError(f"block {p}: the bytecode leaves "
                                     "other slots than the plain procedures")
            self.receipts.append((statuses, gas))
            receipt_roots.append(receipt_root)
        tx_roots = [f.result() for f in tx_roots]
        self.state_roots = state_roots()
        pool.shutdown()

        # -- the chain: every header's commitments are the reference's -----
        genesis = {"parent_hash": bytes(32), "coinbase": bytes(20),
                   "root": self.state_roots[0],
                   "tx_hash": ref_state.EMPTY_ROOT,
                   "receipt_hash": ref_state.EMPTY_ROOT,
                   "bloom": ref_state.NO_BLOOM, "difficulty": 1,
                   "number": 0, "gas_limit": self.gas_limit, "gas_used": 0,
                   "time": 0, "extra": b"geec-genesis", "trust_rand": 0}
        self.genesis_hash = ref_state.keccak256(
            ref_state.header_rlp(genesis))
        self.steps: list = []        # per block: the messages, in order
        self.bad: dict = {}          # block -> its kind of bad block
        self.never_insert: set = set()
        self.block_hashes: list = []  # the canonical block of each height
        self.headers: list = []      # its header's fields, but the author
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
            statuses, gas = self.receipts[p]
            root = self.state_roots[h]
            txs = rlp_list(*self.frames[p * per_blk:(p + 1) * per_blk])
            trust_rand = rng.getrandbits(64)
            sound = {"parent_hash": parent_hash, "root": root,
                     "tx_hash": tx_roots[p],
                     "receipt_hash": receipt_roots[p],
                     "bloom": ref_state.NO_BLOOM, "difficulty": DIFFICULTY,
                     "number": h, "gas_limit": self.gas_limit,
                     "gas_used": gas[-1], "time": h, "extra": b"",
                     "trust_rand": trust_rand}
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
            reverts = sum(1 for s in statuses if not s)

            def block_of(header: dict, txs_enc: bytes, author: bytes):
                enc = ref_state.header_rlp({**header, "coinbase": author})
                return (ref_state.keccak256(enc),
                        rlp_list(enc, b"\xc0", b"\xc0", txs_enc, b"\xc0",
                                 b"\xc0"))

            def request(bhash, block_enc, author, version, *, calls=per_blk,
                        reverts=reverts, **kw):
                job = sign(priv_of[author], b"geec/validate-req" + rlp.encode(
                    [h, author, bhash, version]))
                ip, port = at_of[author]
                step = Step("request", b"", bhash, per_blk + 1, **kw)
                # what an execution of this block runs through the EVM
                step.calls, step.reverts = calls, reverts

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
                steps = [request(bhash, enc, proposers[0], 0),
                         confirm(bhash, proposers[0], bhash, confidence)]
            else:
                twin_hash, twin_enc = block_of(sound, txs, proposers[1])
                header, bad_txs, ran_to = sound, txs, {}
                if bad == "state_root":
                    header = {**sound, "root": parent_root}
                elif bad == "gas_used":
                    header = {**sound, "gas_used": gas[-1] + 1}
                elif bad in ("signature", "nonce_gap"):
                    at = rng.randrange(per_blk)
                    k = rows[at]
                    if bad == "signature":
                        rows[at] = spoil(k, SIG_KINDS[
                            (h // every // len(BAD_KINDS)) % 2])
                        # no sender, no execution (unless a control's
                        # verifier gives one: the driver reads the ACK)
                        ran_to = {"calls": 0, "reverts": 0}
                    else:
                        a = self.account[k]
                        body = transfer_body(
                            nonces[k] + 1, d["call_gas_limit"],
                            self.contract, 0, datas[k])
                        self.frames.append(_frame(body, _sign_bodies(
                            [body], [acc_privs[a]], rng)[0]))
                        self.kind.append("nonce_gap")
                        self.origin.append(k)
                        rows[at] = len(self.frames) - 1
                        ran_to = {"calls": at, "reverts": sum(
                            1 for s in statuses[:at] if not s)}
                    enc = [self.frames[k] for k in rows]
                    bad_txs = rlp_list(*enc)
                    header = {**sound, "tx_hash": ref_state.derive_sha(enc)}
                bhash, enc = block_of(header, bad_txs, proposers[0])
                self.never_insert.add(bhash)
                if bad == "certificate":
                    steps = [request(bhash, enc, proposers[0], 0, bad=bad),
                             confirm(bhash, proposers[0], twin_hash,
                                     confidence, sound=False, bad=bad)]
                else:
                    steps = [request(bhash, enc, proposers[0], 0,
                                     sound=False, bad=bad, **ran_to)]
                steps += [request(twin_hash, twin_enc, proposers[1], 1),
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
    def alloc(self) -> dict:
        """The genesis allocation, under upstream's ``genesis.json``'s
        keys."""
        out: dict = {a: self.balance for a in self.addrs}
        out[self.contract] = {"nonce": 1, "balance": 0, "code": self.code,
                              "storage": self.genesis_storage}
        return out

    def windows(self, block: int) -> list:
        """Block ``block``'s gossip windows, each a list of frame indices."""
        return self.blocks[block]

    def signer(self, k: int) -> bytes:
        """The account that signed the sound frame under frame ``k``."""
        return self.addrs[self.account[self.origin[k]]]

    def frame_expect(self, k: int):
        """What the pool must do with a fresh frame k (``gen_chain``'s)."""
        kind = self.kind[k]
        if kind in (None, "nonce_gap"):
            return "admit", self.signer(k)
        if kind == "flipped_message":
            return "admit_other", self.signer(k)
        return "reject", None

    def state_at(self, height: int) -> dict:
        """The reference's accounts after block ``height``: address ->
        ``(nonce, balance)``, every funded account."""
        out = {a: (0, self.balance) for a in self.addrs}
        for delta in self.deltas[:height]:
            out.update(delta)
        return out

    def balances_at(self, height: int, customers) -> dict:
        """``customer -> (savings, checking)`` after block ``height``."""
        out = {}
        for c in customers:
            pair = []
            for m in (contracts.SAVING, contracts.CHECKING):
                v = self.genesis_storage[self.slot[m, c]]
                for delta in self.slot_deltas[:height]:
                    v = delta.get((m, c), v)
                pair.append(v)
            out[c] = tuple(pair)
        return out

    def construction(self, block: int) -> dict:
        """A block's rows by construction (``gen_chain``'s), and its
        calls: by procedure, on the hot set, aborted, the slots written
        and those of them deleted."""
        seq = [k for w in self.blocks[block] for k in w]
        per_blk = self.d["txn_per_block"]
        own = range(block * per_blk, (block + 1) * per_blk)
        calls = self.calls[own.start:own.stop]
        named = [c for name, args in calls
                 for c in args[:2 if name in TWO_CUSTOMERS else 1]]
        by_name: dict = {}
        for name, _args in calls:
            by_name[name] = by_name.get(name, 0) + 1
        return {"gossip_frames": len(seq),
                "own_in_time": sum(1 for k in set(seq) if k in own),
                "late_of_previous": sum(
                    1 for k in set(seq) if self.kind[k] is None
                    and k not in own),
                "copies": len(seq) - len(set(seq)),
                "spoiled": sum(1 for k in seq if self.kind[k] is not None),
                "unseen_at_request": len(self.unseen[block]),
                "touched_accounts": len(self.deltas[block]),
                "bad": self.bad.get(block),
                "steps": [(s.what, s.rows, s.sound) for s in
                          self.steps[block]],
                "rows_asked": len(seq) + sum(s.rows for s in
                                             self.steps[block]),
                "calls": by_name,
                "hot_share": sum(1 for c in named
                                 if c <= self.d["hot_customers"])
                / len(named),
                "aborted": sum(self.aborted[own.start:own.stop]),
                "slots_written": len(self.slot_deltas[block]),
                "slots_deleted": sum(
                    1 for v in self.slot_deltas[block].values() if not v),
                "call_data_bytes": sorted({
                    len(contracts.call_data(n, *a)) for n, a in calls})}
