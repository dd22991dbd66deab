"""ECDSA signatures whose MESSAGE is known only at run time: a validator's
ACK signs the hash of a block that the node under test has yet to build.

The expensive half of a signature does not depend on the message: the
nonce ``k``, its point ``k*G`` (so ``r``) and ``1/k`` (SEC 1 v2, section
4.1.3, steps 1-3).  :class:`LateSigner` lays those at set-up as
``secp.sign_rows`` lays them (consecutive nonces, one mixed addition a
point, every inversion shared), and :meth:`LateSigner.finish` does what is
left when the message's hash is there: ``s = (z + r*d) / k``, the low-``s``
form and the recovery id.  A row signed here and a row signed by
``secp.sign_rows`` with the same key, nonce and hash are the same 65 bytes.
Imports nothing of the program.
"""

from __future__ import annotations

from perfbench.ref import secp

N = secp.N


class LateSigner:
    def __init__(self, privs: list, k0: int):
        """Row i will be signed by ``privs[i]`` with the nonce ``k0 + i``."""
        pts = secp._walk(k0, len(privs))
        self.kinv = secp.inv_many([(k0 + i) % N for i in range(len(privs))],
                                  N)
        self.r = [x % N for x, _y in pts]
        self.rd = [r * d % N for r, d in zip(self.r, privs)]
        self.recid = [(y & 1) | (2 if x >= N else 0) for x, y in pts]
        self.r_bytes = [r.to_bytes(32, "big") for r in self.r]

    def finish(self, i: int, h: bytes) -> bytes:
        """Row i's signature r || s || recid over the 32-byte hash ``h``."""
        s = self.kinv[i] * (int.from_bytes(h, "big") + self.rd[i]) % N
        recid = self.recid[i]
        if 2 * s > N:
            s, recid = N - s, recid ^ 1
        return self.r_bytes[i] + s.to_bytes(32, "big") + bytes([recid])
