"""The plain reference of a block's state transition and of the roots a
header commits to: what executing a list of plain transfers on a parent
state gives, account for account, and the transaction root, state root,
receipts root, gas used and bloom of the block.  Its own RLP, its own
Keccak (``keccak256_many``, a trie level at a time) and nothing of the
program.

Upstream: ``core/state_processor.go:60-100`` (``Process``: the
transactions in order on the parent state), ``core/state_transition.go``
(``TransitionDb``: nonce, balance, the transfer, the fee),
``core/block_validator.go:82-105`` (``ValidateState``: gas used, bloom,
receipts root, state root), ``core/types/derive_sha.go`` and ``trie/``
(the Merkle-Patricia trie over ``rlp(index)`` keys, the secure trie over
``keccak(address)`` keys).

Where this deployment departs from upstream, each noted at its site:
every transfer carries 1 wei at gas price 0 (no fee reaches a coinbase,
so the block's author touches no account), no account holds code (no
contract runs, no log is written, every receipt's bloom is empty), the
transaction has the fork's ``is_geec`` field (ten fields, the marker
seventh), and ``TX_GAS`` below.
"""

from __future__ import annotations

from perfbench.ref import rlp
from perfbench.ref.keccak import keccak256, keccak256_many

# What a plain transfer to an account without code is charged.  Upstream's
# IntrinsicGas adds 68 a non-zero and 4 a zero byte of call data (about
# 27,800 with 100 B); the PROGRAM charges params.TxGas flat on that path
# (eges_tpu/core/state.py apply_txn) and the data's gas only where the
# EVM runs.  At gas price 0 no balance moves either way; the headers'
# ``gas_used`` and the receipts' cumulative gas follow the program's rule,
# because a chain of upstream's numbers is one the program refuses whole.
TX_GAS = 21_000

EMPTY_ROOT = keccak256(b"\x80")          # the root of a trie with no leaf
EMPTY_CODE_HASH = keccak256(b"")
EMPTY_UNCLES = keccak256(b"\xc0")        # keccak(rlp([]))
NO_BLOOM = bytes(256)


class Refused(Exception):
    """A transaction that cannot be applied: the block is invalid."""


# -- the transition ----------------------------------------------------------

def apply_transfers(state: dict, transfers) -> tuple:
    """``transfers``: ``(sender, nonce, to, value, gas_limit)`` in block
    order, applied to ``state`` (address -> ``[nonce, balance]``) IN
    PLACE.  Returns ``(touched addresses, cumulative gas after each)``;
    raises :class:`Refused` as upstream's ``TransitionDb`` errors (a nonce
    that is not the account's, a gas limit under the intrinsic gas, a
    balance under the value; gas price 0 buys gas for nothing)."""
    touched: set = set()
    gas, cumulative = 0, []
    for sender, nonce, to, value, gas_limit in transfers:
        acct = state.get(sender)
        if acct is None or acct[0] != nonce:
            raise Refused("nonce")
        if gas_limit < TX_GAS:
            raise Refused("intrinsic gas")
        if acct[1] < value:
            raise Refused("balance")
        acct[0] += 1
        acct[1] -= value
        state.setdefault(to, [0, 0])[1] += value
        touched.add(sender)
        touched.add(to)
        gas += TX_GAS
        cumulative.append(gas)
    return touched, cumulative


def account_rlp(nonce: int, balance: int) -> bytes:
    """``[nonce, balance, storage root, code hash]`` of an account that
    holds neither storage nor code."""
    return rlp.encode([nonce, balance, EMPTY_ROOT, EMPTY_CODE_HASH])


def receipt_rlp(status: int, cumulative_gas: int) -> bytes:
    """``[status, cumulative gas, bloom, logs]`` of a transfer: no log."""
    return rlp.encode([status, cumulative_gas, NO_BLOOM, []])


def logs_bloom(values) -> bytes:
    """The 2048-bit bloom of ``values`` (a log's address and topics):
    three bits a value, the low 11 bits of the first three byte pairs of
    its Keccak (``core/types/bloom9.go``).  A block of transfers has no
    value to put in."""
    bits = 0
    for h in keccak256_many(values):
        for i in (0, 2, 4):
            bits |= 1 << (((h[i] << 8) | h[i + 1]) & 2047)
    return bits.to_bytes(256, "big")


# -- the tries ------------------------------------------------------------------

def _hex_prefix(nibbles, leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(nibbles) % 2:
        head, rest = [16 * (flag + 1) + nibbles[0]], nibbles[1:]
    else:
        head, rest = [16 * flag], nibbles
    return bytes(head + [16 * rest[i] + rest[i + 1]
                         for i in range(0, len(rest), 2)])


# a node: [kind, path or children, value or child, ref, parent, height];
# ``ref`` is what stands for the node in its parent's encoding
_KIND, _A, _B, _REF, _PARENT, _HEIGHT = range(6)


def _build(items: list, lo: int, hi: int, depth: int):
    """The node over ``items[lo:hi]`` (sorted ``(nibbles, value)``, all
    alike on their first ``depth`` nibbles).  No key is another's
    beginning (RLP and fixed-width keys are prefix-free), so a branch
    holds no value."""
    if hi - lo == 1:
        return ["leaf", items[lo][0][depth:], items[lo][1], None, None, 0]
    first, last = items[lo][0], items[hi - 1][0]
    cp = depth
    while first[cp] == last[cp]:
        cp += 1
    if cp > depth:
        child = _build(items, lo, hi, cp)
        node = ["ext", first[depth:cp], child, None, None,
                child[_HEIGHT] + 1]
        child[_PARENT] = node
        return node
    children, at = [None] * 16, lo
    node = ["branch", children, None, None, None, 0]
    while at < hi:
        nib, end = items[at][0][depth], at + 1
        while end < hi and items[end][0][depth] == nib:
            end += 1
        child = children[nib] = _build(items, at, end, depth + 1)
        child[_PARENT] = node
        node[_HEIGHT] = max(node[_HEIGHT], child[_HEIGHT] + 1)
        at = end
    return node


def _encode(node) -> bytes:
    if node[_KIND] == "leaf":
        return rlp.encode([_hex_prefix(node[_A], True), node[_B]])
    if node[_KIND] == "ext":
        body = rlp.encode(_hex_prefix(node[_A], False)) + node[_B][_REF]
    else:
        body = b"".join([b"\x80" if c is None else c[_REF]
                         for c in node[_A]]) + b"\x80"
    return rlp.length_prefix(len(body), 0xC0) + body


def _hash_up(levels: list, root) -> bytes:
    """``levels[h]``: the nodes of height ``h`` whose encoding is to be
    taken (their children's stand), from the leaves up, every height
    through ONE ``keccak256_many``.  A node whose encoding is under 32
    bytes stands in its parent as it is, any other as its hash; the root
    is always the hash."""
    for nodes in levels:
        encs = [_encode(n) for n in nodes]
        big = [i for i, e in enumerate(encs) if len(e) >= 32]
        for n, e in zip(nodes, encs):
            n[_REF] = e
        for i, h in zip(big, keccak256_many(encs[i] for i in big)):
            nodes[i][_REF] = b"\xa0" + h
    return keccak256(_encode(root))


def _nibbles(key: bytes) -> list:
    return [b >> s & 15 for b in key for s in (4, 0)]


def _by_height(nodes) -> list:
    levels: list = []
    for n in nodes:
        while len(levels) <= n[_HEIGHT]:
            levels.append([])
        levels[n[_HEIGHT]].append(n)
    return levels


def _all_nodes(root) -> list:
    out, stack = [], [root]
    while stack:
        n = stack.pop()
        out.append(n)
        if n[_KIND] == "ext":
            stack.append(n[_B])
        elif n[_KIND] == "branch":
            stack.extend(c for c in n[_A] if c is not None)
    return out


def trie_root(pairs) -> bytes:
    """The root of the Merkle-Patricia trie that holds ``pairs`` (``(key,
    value)``, keys distinct): built once from the sorted leaves, then
    hashed a height at a time (:func:`_hash_up`)."""
    items = sorted((_nibbles(k), v) for k, v in pairs)
    if not items:
        return EMPTY_ROOT
    root = _build(items, 0, len(items), 0)
    return _hash_up(_by_height(_all_nodes(root)), root)


class SecureState:
    """The secure trie of a state whose SET of accounts never changes:
    every account of the deployment is funded at genesis and none is
    ever emptied, so the trie keeps its shape and a block re-encodes only
    the leaves it touched and the nodes above them (still a height at a
    time through ``keccak256_many``).  ``state_root`` below builds the
    whole trie anew; the tests hold the two to each other."""

    def __init__(self, state: dict, address_keys: dict):
        items = sorted((_nibbles(address_keys[a]), a) for a in state)
        self.root_node = _build(items, 0, len(items), 0)
        self.leaf_of = {}
        nodes = _all_nodes(self.root_node)
        for n in nodes:
            if n[_KIND] == "leaf":
                a = n[_B]
                self.leaf_of[a] = n
                n[_B] = account_rlp(*state[a])
        self._dirty = nodes

    def set(self, address: bytes, nonce: int, balance: int) -> None:
        if not (nonce or balance):
            raise ValueError("an emptied account would leave the trie")
        leaf = self.leaf_of[address]
        leaf[_B] = account_rlp(nonce, balance)
        self._dirty.append(leaf)

    def root(self) -> bytes:
        seen, nodes = set(), []
        for n in self._dirty:
            while n is not None and id(n) not in seen:
                seen.add(id(n))
                nodes.append(n)
                n = n[_PARENT]
        self._dirty = []
        return _hash_up(_by_height(nodes), self.root_node)


def derive_sha(encoded: list) -> bytes:
    """The root over a list's items, item ``i`` under the key ``rlp(i)``
    (``core/types/derive_sha.go``): a block's transactions, its receipts."""
    return trie_root((rlp.encode(i), e) for i, e in enumerate(encoded))


def state_root(state: dict, address_keys: dict) -> bytes:
    """The secure trie's root over every account that is not empty:
    ``keccak(address)`` -> the account's RLP.  ``address_keys`` maps an
    address to its Keccak (taken once for the run: the addresses do not
    change)."""
    return trie_root((address_keys[a], account_rlp(n, b))
                     for a, (n, b) in state.items() if n or b)


def header_rlp(h: dict) -> bytes:
    """A header's seventeen fields in wire order
    (``core/types/block.go:71-90`` with the fork's ``regs`` and
    ``trust_rand``); its Keccak is the block's hash."""
    return rlp.encode([
        h["parent_hash"], EMPTY_UNCLES, h["coinbase"], h["root"],
        h["tx_hash"], h["receipt_hash"], h["bloom"], h["difficulty"],
        h["number"], h["gas_limit"], h["gas_used"], h["time"], h["extra"],
        bytes(32), bytes(8), [], h["trust_rand"]])
