"""Keccak-256 (the Ethereum padding 0x01, not NIST SHA-3's 0x06), for one
message or, through numpy, for a whole batch at once.  Follows the Keccak
reference (Bertoni et al.); no code of the program is used."""

from __future__ import annotations

import numpy as np

RATE = 136  # bytes absorbed per block at a 256-bit capacity pair

_RC = np.array([
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
], dtype=np.uint64)

# rotation offsets r[x][y] of the rho step, lane index = x + 5*y
_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
        [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]


def _rotl(a: np.ndarray, n: int) -> np.ndarray:
    if n == 0:
        return a
    return (a << np.uint64(n)) | (a >> np.uint64(64 - n))


def _permute(st: list) -> list:
    """Keccak-f[1600] on 25 lanes, each a uint64 array over the batch."""
    for rnd in range(24):
        c = [st[x] ^ st[x + 5] ^ st[x + 10] ^ st[x + 15] ^ st[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        st = [st[i] ^ d[i % 5] for i in range(25)]
        b = [None] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl(st[x + 5 * y],
                                                         _ROT[x][y])
        st = [b[i] ^ (~b[(i % 5 + 1) % 5 + 5 * (i // 5)]
                      & b[(i % 5 + 2) % 5 + 5 * (i // 5)])
              for i in range(25)]
        st[0] = st[0] ^ _RC[rnd]
    return st


def keccak256_many(msgs) -> list:
    """The 32-byte digest of every message of ``msgs``, in order."""
    msgs = [bytes(m) for m in msgs]
    out: list = [None] * len(msgs)
    by_blocks: dict[int, list[int]] = {}
    for i, m in enumerate(msgs):
        by_blocks.setdefault(len(m) // RATE + 1, []).append(i)
    for nblk, idx in by_blocks.items():
        width = nblk * RATE
        buf = np.frombuffer(b"".join(
            (msgs[i] + b"\x01").ljust(width, b"\0") for i in idx),
            np.uint8).reshape(len(idx), width).copy()
        buf[:, width - 1] ^= 0x80
        lanes = buf.view("<u8").reshape(len(idx), nblk, RATE // 8)
        st = [np.zeros(len(idx), np.uint64) for _ in range(25)]
        for blk in range(nblk):
            for j in range(RATE // 8):
                st[j] = st[j] ^ lanes[:, blk, j]
            st = _permute(st)
        dig = np.stack(st[:4], axis=1).astype("<u8").view(np.uint8)
        for k, i in enumerate(idx):
            out[i] = dig[k].tobytes()
    return out


def keccak256(msg: bytes) -> bytes:
    return keccak256_many([msg])[0]
