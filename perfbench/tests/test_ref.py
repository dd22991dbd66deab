"""The plain reference against public vectors and a second witness."""

import random

import pytest

from perfbench import gen
from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256, keccak256_many


def test_keccak_public_vectors():
    assert keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")
    assert keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45")
    # block boundaries: 135, 136 and 137 bytes take one, two and two blocks
    many = keccak256_many([b"a" * n for n in (135, 136, 137)])
    assert many == [keccak256(b"a" * n) for n in (135, 136, 137)]
    assert len(set(many)) == 3


def test_address_of_private_key_one():
    _, (addr,) = secp.keys(1, 1)
    assert addr.hex() == "7e5f4552091a69125d5dfcb7b8c2659029395bdf"


def test_rlp_public_vectors():
    assert rlp.encode(b"dog") == b"\x83dog"
    assert rlp.encode([b"cat", b"dog"]) == b"\xc8\x83cat\x83dog"
    assert rlp.encode(0) == b"\x80" and rlp.encode(1024) == b"\x82\x04\x00"
    assert rlp.encode(b"a" * 56)[:2] == b"\xb8\x38"


def test_signatures_recover_and_satisfy_a_second_witness():
    """sign_rows' cheap signatures are ordinary ECDSA: the reference
    recovers their signers, and OpenSSL (``cryptography``) verifies them."""
    rng = random.Random(5)
    privs, addrs = secp.keys(rng.randrange(1 << 200), 6)
    hashes = [rng.randbytes(32) for _ in range(6)]
    sigs = secp.sign_rows(privs, hashes, rng.randrange(1 << 200))
    for d, a, h, s in zip(privs, addrs, hashes, sigs):
        assert secp.recover(h, s) == a
        assert int.from_bytes(s[32:64], "big") * 2 < secp.N  # low s
    ec = pytest.importorskip(
        "cryptography.hazmat.primitives.asymmetric.ec")
    from cryptography.hazmat.primitives import hashes as chashes
    from cryptography.hazmat.primitives.asymmetric import utils
    for d, h, s in zip(privs, hashes, sigs):
        pub = ec.derive_private_key(d, ec.SECP256K1()).public_key()
        der = utils.encode_dss_signature(int.from_bytes(s[:32], "big"),
                                         int.from_bytes(s[32:64], "big"))
        pub.verify(der, h, ec.ECDSA(utils.Prehashed(chashes.SHA256())))


def test_recover_refuses_what_is_no_signature_and_the_control_does_not():
    privs, addrs = secp.keys(77, 1)
    h = bytes(range(32))
    sig, = secp.sign_rows(privs, [h], 1234567)
    bad_s = sig[:32] + (secp.N + 9).to_bytes(32, "big") + sig[64:]
    bad_v = sig[:64] + b"\x05"
    off = sig  # find an abscissa off the curve
    off = gen._off_curve_x(random.Random(1)).to_bytes(32, "big") + sig[32:]
    for s in (bad_s, bad_v, off):
        assert secp.recover(h, s) is None
        assert secp.recover(h, s, checked=False) is not None
    flipped = bytes([h[0] ^ 0x40]) + h[1:]
    assert secp.recover(flipped, sig) not in (None, addrs[0])
