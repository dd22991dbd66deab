"""Flagship model: batched secp256k1 sender recovery.

One shared definition of the jittable forward step and its example
inputs, used by ``__graft_entry__.entry()`` and tests — so "the
model" the driver compiles is exactly what the consensus layer runs (ref: the cgo hot path it
replaces, crypto/secp256k1/secp256.go:105 +
core/types/transaction_signing.go:222-241).
"""

from __future__ import annotations

import secrets


def flagship_forward():
    """The jittable forward step: ``(sigs [N,65] u8, hashes [N,32] u8)
    -> (addrs [N,20] u8, pubs [N,64] u8, ok [N] u32)``."""
    from eges_tpu.crypto.verifier import ecrecover_batch

    return ecrecover_batch


def example_batch(n: int, invalid_every: int = 0, n_keys: int = 64):
    """Build an ``n``-row workload of real signatures (plus optional
    invalid rows every ``invalid_every``) with the expected addresses.

    Returns ``(sigs [n,65] u8, hashes [n,32] u8, valid [n] bool,
    expect list[bytes|None])`` — ``expect[i]`` is None for rows whose
    recovered address is defined but differs (corrupted-s rows).
    """
    import numpy as np

    from eges_tpu.crypto import secp256k1 as host

    n_keys = min(n_keys, max(n, 1))
    msgs = [secrets.token_bytes(32) for _ in range(n_keys)]
    privs = [secrets.token_bytes(32) for _ in range(n_keys)]
    sig_cache = [np.frombuffer(host.ecdsa_sign(m, p), np.uint8)
                 for m, p in zip(msgs, privs)]
    addr_cache = [host.pubkey_to_address(host.privkey_to_pubkey(p))
                  for p in privs]

    sigs = np.zeros((n, 65), np.uint8)
    hashes = np.zeros((n, 32), np.uint8)
    valid = np.ones(n, bool)
    expect: list = [b""] * n
    for i in range(n):
        k = i % n_keys
        sigs[i] = sig_cache[k]
        hashes[i] = np.frombuffer(msgs[k], np.uint8)
        expect[i] = addr_cache[k]
        if invalid_every and i % invalid_every == 5:
            valid[i] = False
            if i % 2:
                sigs[i, 40] ^= 0xFF  # corrupt s: recovers a wrong address
                expect[i] = None
            else:
                sigs[i, 64] = 9       # invalid recovery id: masked row
                expect[i] = b"\0" * 20
    return sigs, hashes, valid, expect
