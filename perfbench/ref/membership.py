"""The plain reference of WHO may propose and who may certify a height:
the seeded windows over the sorted member list, as upstream's
``getAllCommittee`` (``core/geec_state.go:358-419``) cuts them and
``eges_tpu/consensus/membership.py`` ports them.  Nothing of the program
is used; ``tests/test_acceptor_path.py`` holds this copy to
``Membership`` at small size.

Members sorted by address; ``start = seed % size``; the window is the
``n`` members from ``start`` on, wrapping to the list's beginning; with
fewer than ``n`` members everyone is in.  The committee (who may propose)
takes ``n_candidates`` and, for a re-election at ``version`` > 0, the seed
``seed ** (version + 1) mod (2**64 - 59)``; the acceptors (who may ACK and
whose signatures a certificate counts) take ``n_acceptors`` and the
height's seed as it is.  The seed of height ``h`` is the ``trust_rand``
of block ``h - 1``'s header.
"""

from __future__ import annotations

SEED_MOD = (1 << 64) - 59  # the largest 64-bit prime


def derive_seed(seed: int, version: int) -> int:
    return seed if version == 0 else pow(seed, version + 1, SEED_MOD)


def window(sorted_addrs: list, seed: int, n: int) -> list:
    size = len(sorted_addrs)
    if size <= n:
        return list(sorted_addrs)
    start = seed % size
    if start + n > size:
        return sorted_addrs[:n - size + start] + sorted_addrs[start:]
    return sorted_addrs[start:start + n]


def committee(sorted_addrs: list, seed: int, version: int,
              n_candidates: int) -> list:
    return window(sorted_addrs, derive_seed(seed, version), n_candidates)


def acceptors(sorted_addrs: list, seed: int, n_acceptors: int) -> list:
    return window(sorted_addrs, seed, n_acceptors)


def majority(n_acceptors: int, members: int) -> int:
    """Upstream's quorum where the chain configures no fraction:
    ``ceil((acceptors + 1) / 2)`` of the acceptors there are."""
    return -(-(min(members, n_acceptors) + 1) // 2)
