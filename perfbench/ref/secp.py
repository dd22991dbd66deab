"""secp256k1 in plain Python integers: key derivation, ECDSA signing and
public-key recovery as SEC 1 v2 (sections 4.1.3 and 4.1.6) gives them, and
the Ethereum address of a key.  Imports nothing of the program.

``sign_rows`` signs many rows cheaply: row i uses the nonce k0 + i, so that
its point is the previous row's plus G (one mixed addition), and every
inversion of the batch is shared (Montgomery's trick).  The nonces are
predictable, which matters to nobody: the keys guard nothing.
"""

from __future__ import annotations

from perfbench.ref.keccak import keccak256_many

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8


def inv_many(vals: list, mod: int) -> list:
    """The inverse of every (non-zero) value, for one modular inversion."""
    pre, acc = [], 1
    for v in vals:
        pre.append(acc)
        acc = acc * v % mod
    acc = pow(acc, -1, mod)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = acc * pre[i] % mod
        acc = acc * vals[i] % mod
    return out


def _double(p):
    x, y, z = p
    if not y or not z:
        return (0, 1, 0)
    s = 4 * x * y * y % P
    m = 3 * x * x % P
    x2 = (m * m - 2 * s) % P
    return (x2, (m * (s - x2) - 8 * pow(y, 4, P)) % P, 2 * y * z % P)


def _add(p, q):
    """Jacobian addition, any two points."""
    if not p[2]:
        return q
    if not q[2]:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1s, z2s = z1 * z1 % P, z2 * z2 % P
    u1, u2 = x1 * z2s % P, x2 * z1s % P
    s1, s2 = y1 * z2s * z2 % P, y2 * z1s * z1 % P
    if u1 == u2:
        return _double(p) if s1 == s2 else (0, 1, 0)
    h, r = (u2 - u1) % P, (s2 - s1) % P
    hs = h * h % P
    hc = hs * h % P
    x3 = (r * r - hc - 2 * u1 * hs) % P
    return (x3, (r * (u1 * hs - x3) - s1 * hc) % P, h * z1 * z2 % P)


def _mul(k: int, p):
    acc = (0, 1, 0)
    for bit in bin(k % N)[2:]:
        acc = _double(acc)
        if bit == "1":
            acc = _add(acc, p)
    return acc


def _affine(p):
    if not p[2]:
        return None
    zi = pow(p[2], -1, P)
    return (p[0] * zi * zi % P, p[1] * zi * zi * zi % P)


def _walk(start: int, count: int) -> list:
    """The affine points start*G, (start+1)*G, ...: one mixed addition and
    a share of one inversion each."""
    jac, cur = [], _mul(start, (GX, GY, 1))
    g = (GX, GY, 1)
    for _ in range(count):
        jac.append(cur)
        cur = _add(cur, g)
    zi = inv_many([p[2] for p in jac], P)
    return [(p[0] * z * z % P, p[1] * z * z % P * z % P)
            for p, z in zip(jac, zi)]


def address_of(pub) -> bytes:
    return addresses_of([pub])[0]


def addresses_of(pubs) -> list:
    return [d[12:] for d in keccak256_many(
        x.to_bytes(32, "big") + y.to_bytes(32, "big") for x, y in pubs)]


def keys(first: int, count: int):
    """``count`` private keys first, first+1, ... with their addresses."""
    return (list(range(first, first + count)),
            addresses_of(_walk(first, count)))


def sign_rows(privs: list, hashes: list, k0: int) -> list:
    """One 65-byte signature r || s || recid (low s) for each row."""
    pts = _walk(k0, len(privs))
    kinv = inv_many([(k0 + i) % N for i in range(len(privs))], N)
    out = []
    for (x, y), ki, d, h in zip(pts, kinv, privs, hashes):
        r = x % N
        s = ki * (int.from_bytes(h, "big") + r * d) % N
        recid = (y & 1) | (2 if x >= N else 0)
        if 2 * s > N:
            s, recid = N - s, recid ^ 1
        out.append(r.to_bytes(32, "big") + s.to_bytes(32, "big")
                   + bytes([recid]))
    return out


def recover(h: bytes, sig: bytes, *, checked: bool = True):
    """The address that signed hash ``h``, or None where the signature is
    not one: r or s outside [1, N-1], a recovery id outside 0..3, r not
    the abscissa of a curve point, or a key at infinity.  ``checked=False``
    is the control: it reduces r and s into range, reads the id modulo 4
    and, where r is off the curve, takes the next abscissa that is on it,
    and so answers every row."""
    if len(h) != 32 or len(sig) != 65:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:64], "big")
    recid = sig[64]
    if not checked:
        r, s, recid = r % N or 1, s % N or 1, recid % 4
    elif not (0 < r < N and 0 < s < N and recid < 4):
        return None
    x = r + (N if recid & 2 else 0)
    while True:
        if x >= P:
            return None
        ysq = (pow(x, 3, P) + 7) % P
        y = pow(ysq, (P + 1) // 4, P)
        if y * y % P == ysq:
            break
        if checked:
            return None
        x += 1
    if (y & 1) != (recid & 1):
        y = P - y
    ri = pow(r, -1, N)
    z = int.from_bytes(h, "big")
    q = _affine(_add(_mul(s * ri % N, (x, y, 1)),
                     _mul(-z * ri % N, (GX, GY, 1))))
    return None if q is None else address_of(q)
