"""Published peaks by ``device_kind``, and the work of one recover window
computed from its shape.  A device that is not in the table is an error.

No roofline share is reported yet: the recover program is u32 limb
arithmetic on the vector unit, for which none of the published peaks is a
ceiling, and at 117 bytes a row the memory bound is vacuous.  The work
function is here so that a run can print achieved u32 multiply-adds a
second; the share waits for a measured ceiling (PERF.md, Open questions).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12, "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

# One public-key recovery by the textbook route, in 256-bit modular
# multiplications: the square root for y (255 squarings, 13 products),
# 1/r mod n (256 + 128), two 256-bit scalar multiplications by
# double-and-add in Jacobian coordinates (256 doublings of 8, 128
# additions of 11, each), one inversion to affine (384).  A product of
# two 16-limb numbers is 256 limb multiply-adds and its reduction about
# 64 more.  Keccak's 24 rounds of 64-bit logic are left out (under 1%).
FIELD_MULS_PER_ROW = 268 + 384 + 2 * (256 * 8 + 128 * 11) + 384
LIMB_MACS_PER_FIELD_MUL = 16 * 16 + 64
BYTES_PER_ROW = 65 + 32 + 20


def recover_work(rows: int) -> dict:
    """u32 limb multiply-adds and HBM bytes that ``rows`` recoveries need."""
    return {"u32_mac": rows * FIELD_MULS_PER_ROW * LIMB_MACS_PER_FIELD_MUL,
            "bytes": rows * BYTES_PER_ROW}


def achieved(reduced: dict, rows: int, device_kind: str):
    """Achieved u32 multiply-adds a second of the recover program in a
    reduced trace, or None where the trace names no such program."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    if not reduced.get("program_s") or rows <= 0:
        return None
    return recover_work(rows)["u32_mac"] / reduced["program_s"]
