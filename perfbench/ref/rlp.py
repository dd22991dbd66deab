"""Canonical RLP encoding of byte strings, non-negative integers and lists
(Ethereum yellow paper, appendix B)."""

from __future__ import annotations


def length_prefix(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    lb = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([offset + 55 + len(lb)]) + lb


def encode(item) -> bytes:
    if isinstance(item, int):
        item = item.to_bytes((item.bit_length() + 7) // 8, "big")
    if isinstance(item, (bytes, bytearray)):
        if len(item) == 1 and item[0] < 0x80:
            return bytes(item)
        return length_prefix(len(item), 0x80) + bytes(item)
    body = b"".join(encode(x) for x in item)
    return length_prefix(len(body), 0xC0) + body
