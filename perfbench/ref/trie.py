"""A plain Merkle-Patricia trie that changes shape: insert, delete, and a
hash kept at every node until something under it changes.  Yellow paper,
appendix D; upstream ``trie/trie.go`` (``insert``, ``delete``) and
``trie/hasher.go`` (a node under 32 bytes stands in its parent as it is,
any other as its Keccak; the root always as its Keccak).  Its own RLP and
Keccak (``ref/rlp.py``, ``ref/keccak.py``), nothing of the program, and
nothing of ``ref/state.py``, whose trie is built once and keeps its shape.

A node is a list: ``[LEAF, path, value, ref, version]``, ``[EXT, path,
child, ref, version]`` or ``[BRANCH, children(16), None, ref, version]``;
``ref`` is what stands for the node in its parent (None until it is
hashed).  Keys are of one length, so no key is another's beginning and a
branch holds no value.  The structure is walked recursively, a key at a
time.  A trie can be COMMITTED, version after version (a block's writes
each): a committed version's nodes are never changed again (a later write
copies the nodes of its path that an older version made, once a version),
so every version's top node stays, and the hashing can wait: all nodes
that lack a reference, of every version at once, are hashed from the
deepest up, each depth through ONE ``keccak256_many`` (a digest alone costs
what a few thousand cost together).
"""

from __future__ import annotations

from perfbench.ref import rlp
from perfbench.ref.keccak import keccak256, keccak256_many

LEAF, EXT, BRANCH = 0, 1, 2
_KIND, _A, _B, _REF, _VER = range(5)
EMPTY_ROOT = keccak256(b"\x80")


def nibbles(key: bytes) -> tuple:
    return tuple(b >> s & 15 for b in key for s in (4, 0))


def hex_prefix(path, leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(path) % 2:
        head, rest = [16 * (flag + 1) + path[0]], path[1:]
    else:
        head, rest = [16 * flag], path
    return bytes(head + [16 * rest[i] + rest[i + 1]
                         for i in range(0, len(rest), 2)])


def _common(a, b) -> int:
    n = 0
    while n < len(a) and n < len(b) and a[n] == b[n]:
        n += 1
    return n


def _mine(node, ver: int):
    """``node`` if version ``ver`` made it, else a copy that ``ver`` may
    change; either way without a reference."""
    if node[_VER] == ver:
        node[_REF] = None
        return node
    return [node[_KIND],
            list(node[_A]) if node[_KIND] == BRANCH else node[_A],
            node[_B], None, ver]


def _insert(node, path: tuple, value: bytes, ver: int):
    """The node that stands where ``node`` stood once ``path`` (what is
    left of the key below it) holds ``value``."""
    if node is None:
        return [LEAF, path, value, None, ver]
    if node[_KIND] == BRANCH:
        node = _mine(node, ver)
        node[_A][path[0]] = _insert(node[_A][path[0]], path[1:], value, ver)
        return node
    mine = node[_A]
    cp = _common(mine, path)
    if cp == len(mine):
        node = _mine(node, ver)
        if node[_KIND] == LEAF:      # the same key: keys are of one length
            node[_B] = value
        else:
            node[_B] = _insert(node[_B], path[cp:], value, ver)
        return node
    # the paths part at ``cp``: a branch there, under an extension if
    # they share a beginning
    children = [None] * 16
    if node[_KIND] == LEAF:
        children[mine[cp]] = [LEAF, mine[cp + 1:], node[_B], None, ver]
    elif len(mine) == cp + 1:
        children[mine[cp]] = node[_B]
    else:
        children[mine[cp]] = [EXT, mine[cp + 1:], node[_B], None, ver]
    children[path[cp]] = [LEAF, path[cp + 1:], value, None, ver]
    branch = [BRANCH, children, None, None, ver]
    return [EXT, mine[:cp], branch, None, ver] if cp else branch


def _joined(nib: int, child, ver: int):
    """What a branch left with the one child ``child`` at ``nib`` becomes:
    the child with the nibble put before its path (upstream's
    ``delete``: a short node in the full node's place)."""
    if child[_KIND] == BRANCH:
        return [EXT, (nib,), child, None, ver]
    return [child[_KIND], (nib,) + child[_A], child[_B], None, ver]


_ABSENT = object()  # _delete's answer where the key was not there


def _delete(node, path: tuple, ver: int):
    """The node that stands where ``node`` stood once the key is gone
    (None: nothing), or ``_ABSENT`` where the key was not there and
    nothing changed."""
    if node is None:
        return _ABSENT
    if node[_KIND] == LEAF:
        return None if node[_A] == path else _ABSENT
    if node[_KIND] == EXT:
        mine = node[_A]
        if path[:len(mine)] != mine:
            return _ABSENT
        child = _delete(node[_B], path[len(mine):], ver)
        if child is _ABSENT:
            return _ABSENT
        if child[_KIND] == BRANCH:   # a branch never goes away whole
            node = _mine(node, ver)
            node[_B] = child
            return node
        return [child[_KIND], mine + child[_A], child[_B], None, ver]
    child = _delete(node[_A][path[0]], path[1:], ver)
    if child is _ABSENT:
        return _ABSENT
    node = _mine(node, ver)
    node[_A][path[0]] = child
    left = [i for i, c in enumerate(node[_A]) if c is not None]
    return _joined(left[0], node[_A][left[0]], ver) if len(left) == 1 \
        else node


def _encode(node) -> bytes:
    if node[_KIND] == LEAF:
        return rlp.encode([hex_prefix(node[_A], True), node[_B]])
    if node[_KIND] == EXT:
        body = rlp.encode(hex_prefix(node[_A], False)) + node[_B][_REF]
    else:
        body = b"".join([b"\x80" if c is None else c[_REF]
                         for c in node[_A]]) + b"\x80"
    return rlp.length_prefix(len(body), 0xC0) + body


def refer(tops) -> None:
    """Give every node under ``tops`` (top nodes, of one version or of
    many) that lacks one its ``ref``, the deepest first.  A node that
    versions share stands as deep as the deepest of them has it: its
    children are then deeper still."""
    depth: dict = {}     # id(node) -> how deep it stands
    nodes: dict = {}
    stack = [(t, 0) for t in tops if t is not None and t[_REF] is None]
    while stack:
        node, d = stack.pop()
        if depth.get(id(node), -1) >= d:
            continue
        depth[id(node)], nodes[id(node)] = d, node
        below = ([node[_B]] if node[_KIND] == EXT
                 else node[_A] if node[_KIND] == BRANCH else ())
        stack.extend((c, d + 1) for c in below
                     if c is not None and c[_REF] is None)
    by_depth: list = []
    for key, d in depth.items():
        while len(by_depth) <= d:
            by_depth.append([])
        by_depth[d].append(nodes[key])
    for level in reversed(by_depth):
        encs = [_encode(n) for n in level]
        big = [i for i, e in enumerate(encs) if len(e) >= 32]
        for n, e in zip(level, encs):
            n[_REF] = e
        for i, h in zip(big, keccak256_many(encs[i] for i in big)):
            level[i][_REF] = b"\xa0" + h


class Trie:
    """``set(key, value)`` (an empty value deletes), ``get``, ``root()``;
    ``commit()`` closes a version, whose top node ``tops`` keeps.  Keys
    are byte strings of one length."""

    def __init__(self):
        self.node = None
        self.tops: list = []     # the committed versions' top nodes

    def set(self, key: bytes, value: bytes) -> None:
        path, ver = nibbles(key), len(self.tops)
        if value:
            self.node = _insert(self.node, path, value, ver)
        elif (node := _delete(self.node, path, ver)) is not _ABSENT:
            self.node = node

    def commit(self) -> None:
        self.tops.append(self.node)

    def get(self, key: bytes):
        node, path = self.node, nibbles(key)
        while node is not None:
            if node[_KIND] == BRANCH:
                node, path = node[_A][path[0]], path[1:]
            elif path[:len(node[_A])] != node[_A]:
                return None
            elif node[_KIND] == LEAF:
                return node[_B]
            else:
                node, path = node[_B], path[len(node[_A]):]
        return None

    def root(self) -> bytes:
        return root_under(self.node)


def root_under(top) -> bytes:
    """The root hash of the trie under the top node ``top``."""
    if top is None:
        return EMPTY_ROOT
    refer([top])
    ref = top[_REF]
    return ref[1:] if len(ref) == 33 else keccak256(ref)


def root_of(pairs) -> bytes:
    """The root over ``(key, value)`` pairs, inserted one by one."""
    t = Trie()
    for k, v in pairs:
        t.set(k, v)
    return t.root()
