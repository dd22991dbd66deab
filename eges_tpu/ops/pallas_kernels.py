"""Pallas TPU kernels for the bignum hot loop (SURVEY §7 step 1:
"secp256k1 batch ops as JAX/Pallas kernels").

The XLA graph form of the verifier (ops/bigint.py, ops/ec.py) already
keeps everything fused on-device, but it pays twice for being a graph:
~66k StableHLO ops (45-85 s compiles) and per-op dispatch granularity.
Round-4 measurement on the live chip showed dispatch is the WHOLE
story on this backend (~40-100 us per executed kernel): the plain
graph ran 20 verifies/s at 256 rows, and a first 2-kernel-per-window
variant only 3.5x that.  So these kernels fuse entire LOOPS, not
steps, each a single ``pallas_call`` whose grid streams per-iteration
operands while the carried state stays resident in VMEM/output refs:

* ``strauss_tab``: the whole 33-window GLV/Strauss ladder (4 doublings
  + 4 conditional mixed adds per window) with IN-KERNEL one-hot table
  lookups — fixed-base operands from trace-time constants, the R
  tables VMEM-resident across the window walk.
* ``pow_mod_pallas``: constant-exponent windowed pow (a^e mod P or
  mod N) — covers FP.sqrt, FP inverse and FN inverse, replacing three
  rolled 256-bit square-and-multiply ladders.
* ``keccak_block_pallas``: the single-block Keccak-f[1600] of the
  address-derivation tail, all 24 rounds in one kernel.
* the GLUE kernels (``fp_add/sub/neg/mul_small/canon``, ``fn_sub/neg/
  red17``, ``mulhi8``): after the loops were fused, the recover graph
  STILL executed as ~3.8k XLA fusions of prelude/GLV/pack/finish
  arithmetic, each its own dispatch — 97% of
  batch wall time.  Routing every remaining field-op call site through
  a one-launch kernel took the chip from 826.8 to 33.5k verifies/s at
  4096 rows (54.0k/s at 16384) in the round-4 A/B.

Layout: the graph stores a field element as ``[B, 16]`` u32 limbs (rows
on sublanes).  Kernels TRANSPOSE to ``[16, B]`` — 16 limbs land exactly
on two 8-sublane rows and the batch rides the 128-wide lane axis, so
every limb row is one natural VPU vector.  The in-kernel field library
(``_k_*``) mirrors ``bigint.FieldP`` bit-for-bit — same fold constants,
same carry chains, same relaxed representation — so kernel and graph
agree exactly.  Testing strategy (tests/test_pallas_kernels.py): the
small F_P-mul kernel is differential-tested through ``pallas_call`` in
interpret mode (covering the shared tiling/transpose plumbing); the
fused ladder kernels' MATH is differential-tested in pure numpy via the
``xp`` namespace parameter (identical uint32 wrap semantics, runs in
milliseconds where interpret-mode XLA compiles of the flat graphs take
tens of minutes); the kernels themselves are compiled by Mosaic for a
described v5e in ``tests/test_chip_compile.py`` and run end-to-end only
on a real TPU, where ``chip_smoke.py`` checks them row for row against
the native C++ recover.

Dispatch: ``EGES_TPU_PALLAS=1`` keeps the historical per-multiply
kernel hook in ``FieldP.mul``; ``EGES_TPU_PALLAS=ladder`` routes the
ladder, the three pow ladders and the keccak tail through the fused
kernels — on the TPU backend only (interpret mode lowers kernels back
to per-block HLO, which would re-explode the CPU graph the rolled
loops were built to avoid).

Ref role: crypto/secp256k1/libsecp256k1/src/ecmult_impl.h (the windowed
ladder the reference runs in C); consumed by secp256.go:105.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from eges_tpu.ops.bigint import MASK, NLIMBS, P, int_to_limbs

# Batch columns per kernel grid step.  Env-tunable for hardware A/B:
# larger blocks mean fewer grid steps (and more VMEM per step — the
# strauss_tab tables cost 3 x 1 KB per column).  256 is the proven
# default; override with EGES_TPU_LANE_BLOCK=1024 to test.
LANE_BLOCK = int(os.environ.get("EGES_TPU_LANE_BLOCK", "256"))
if LANE_BLOCK <= 0 or LANE_BLOCK % 128:
    raise ValueError(
        f"EGES_TPU_LANE_BLOCK={LANE_BLOCK}: must be a positive multiple "
        "of 128 (TPU lane width)")

_P_LIMBS = [int(v) for v in int_to_limbs(P)]
_SUBC_LIMBS = [int(v) for v in int_to_limbs((1 << 256) - 2 * ((1 << 256) - P) + 1)]
_ONE_LIMBS = [1] + [0] * 15


# ---------------------------------------------------------------------------
# in-kernel field library: a value is a Python list of 16 [B]-wide u32
# vectors (limb-major).  Bit-identical to bigint.FieldP's relaxed form.
# ---------------------------------------------------------------------------

def _k_carry_tail(cols, xp=jnp):
    """16 columns (each < 2^31) -> relaxed 16-limb value; the shared
    reduction tail of ``FieldP._reduce_cols`` (two full carry chains +
    delta folds + the closing 5-step mini-chain).

    All ``_k_*`` helpers take an array namespace ``xp``: ``jnp`` when
    tracing inside a kernel, ``numpy`` in the differential tests — the
    flat unrolled math is far too large for XLA CPU to compile in
    reasonable time (compile cost grows superlinearly in flat-graph
    size; measured 9 s for one in-kernel multiply, 84 s for four), but
    numpy executes it in milliseconds with the exact same uint32 wrap
    semantics, pinning the math bit-for-bit against the graph path.
    """
    mask = xp.uint32(MASK)
    c977 = xp.uint32(977)
    out = []
    c = xp.zeros_like(cols[0])
    for k in range(16):
        t = cols[k] + c
        out.append(t & mask)
        c = t >> 16
    out[0] = out[0] + c * c977
    out[2] = out[2] + c
    c = xp.zeros_like(c)
    for k in range(16):
        t = out[k] + c
        out[k] = t & mask
        c = t >> 16
    out[0] = out[0] + c * c977
    out[2] = out[2] + c
    cc = xp.zeros_like(c)
    for k in range(5):
        t = out[k] + cc
        out[k] = t & mask
        cc = t >> 16
    return out


def _k_mul(a, b, xp=jnp):  # api: _k_mul
    """Schoolbook 16x16 product columns + delta folds + carry tail
    (mirrors ``big_mul_cols`` + ``FieldP._reduce_cols``)."""
    mask = xp.uint32(MASK)
    c977 = xp.uint32(977)
    zero = xp.zeros_like(a[0])
    cols = [zero] * 32
    for i in range(NLIMBS):
        ai = a[i]
        for j in range(NLIMBS):
            p = ai * b[j]
            cols[i + j] = cols[i + j] + (p & mask)
            cols[i + j + 1] = cols[i + j + 1] + (p >> 16)
    # fold columns >= 16 via delta = 2^32 + 977 (two passes suffice)
    for _ in range(2):
        if len(cols) <= 16:
            break
        hi = cols[16:]
        lo = cols[:16] + [zero] * max(0, len(hi) + 2 - 16)
        for j, h in enumerate(hi):
            lo[j] = lo[j] + h * c977
            lo[j + 2] = lo[j + 2] + h
        cols = lo[: max(16, len(hi) + 2)]
    return _k_carry_tail(cols, xp)


def _k_sqr(a, xp=jnp):
    return _k_mul(a, a, xp)


def _k_add(a, b, xp=jnp):
    return _k_carry_tail([x + y for x, y in zip(a, b)], xp)


def _k_sub(a, b, xp=jnp):
    """Branchless a - b: a + (0xFFFF - b) + (2^256 - 2*delta + 1),
    mirroring ``FieldP.sub``."""
    mask = xp.uint32(MASK)
    return _k_carry_tail([
        x + (mask - y) + xp.uint32(_SUBC_LIMBS[k])
        for k, (x, y) in enumerate(zip(a, b))], xp)


def _k_neg(a, xp=jnp):
    return _k_sub([xp.zeros_like(v) for v in a], a, xp)


def _k_mul_small(a, k: int, xp=jnp):
    assert k < 16
    return _k_carry_tail([v * xp.uint32(k) for v in a], xp)


def _k_is_zero_mod(a, xp=jnp):
    """Relaxed a ≡ 0 (mod P): exactly 0 or exactly P (u32 0/1 vector)."""
    z = a[0] == 0
    p = a[0] == xp.uint32(_P_LIMBS[0])
    for k in range(1, 16):
        z = z & (a[k] == 0)
        p = p & (a[k] == xp.uint32(_P_LIMBS[k]))
    return (z | p).astype(xp.uint32)


def _k_select(flag, a, b, xp=jnp):
    """flag ? a : b, flag a [B] u32 0/1 vector."""
    f = flag.astype(bool)
    return [xp.where(f, x, y) for x, y in zip(a, b)]


def _k_jac_double(X1, Y1, Z1, xp=jnp):
    """Mirror of ``ec.jac_double`` (dbl-2009-l, a=0)."""
    A = _k_sqr(X1, xp)
    B = _k_sqr(Y1, xp)
    C = _k_sqr(B, xp)
    t = _k_sqr(_k_add(X1, B, xp), xp)
    D = _k_mul_small(_k_sub(_k_sub(t, A, xp), C, xp), 2, xp)
    E = _k_mul_small(A, 3, xp)
    F = _k_sqr(E, xp)
    X3 = _k_sub(F, _k_mul_small(D, 2, xp), xp)
    Y3 = _k_sub(_k_mul(E, _k_sub(D, X3, xp), xp), _k_mul_small(C, 8, xp), xp)
    Z3 = _k_mul_small(_k_mul(Y1, Z1, xp), 2, xp)
    return X3, Y3, Z3


def _k_jac_add_mixed(X1, Y1, Z1, x2, y2, xp=jnp):
    """Mirror of ``ec.jac_add_mixed`` (madd-2007-bl + branchless
    exceptional cases)."""
    Z1Z1 = _k_sqr(Z1, xp)
    U2 = _k_mul(x2, Z1Z1, xp)
    S2 = _k_mul(_k_mul(y2, Z1, xp), Z1Z1, xp)
    H = _k_sub(U2, X1, xp)
    r = _k_sub(S2, Y1, xp)

    HH = _k_sqr(H, xp)
    I = _k_mul_small(HH, 4, xp)
    J = _k_mul(H, I, xp)
    rr = _k_mul_small(r, 2, xp)
    V = _k_mul(X1, I, xp)
    X3 = _k_sub(_k_sub(_k_sqr(rr, xp), J, xp), _k_mul_small(V, 2, xp), xp)
    Y3 = _k_sub(_k_mul(rr, _k_sub(V, X3, xp), xp),
                _k_mul_small(_k_mul(Y1, J, xp), 2, xp), xp)
    Z3 = _k_mul(_k_mul_small(Z1, 2, xp), H, xp)

    DX, DY, DZ = _k_jac_double(X1, Y1, Z1, xp)

    h0 = _k_is_zero_mod(H, xp)
    r0 = _k_is_zero_mod(r, xp)
    p1_inf = _k_is_zero_mod(Z1, xp)
    dbl = h0 * r0
    opp = h0 * (1 - r0)

    onef = [xp.broadcast_to(xp.uint32(v), X1[0].shape)
            for v in _ONE_LIMBS]
    zerof = [xp.zeros_like(v) for v in X1]
    X = _k_select(dbl, DX, X3, xp)
    Y = _k_select(dbl, DY, Y3, xp)
    Z = _k_select(dbl, DZ, Z3, xp)
    Z = _k_select(opp, zerof, Z, xp)
    Y = _k_select(opp, onef, Y, xp)
    X = _k_select(p1_inf, x2, X, xp)
    Y = _k_select(p1_inf, y2, Y, xp)
    Z = _k_select(p1_inf, onef, Z, xp)
    return X, Y, Z


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _read16(ref):
    return [ref[k, :] for k in range(NLIMBS)]


def _write16(ref, val):
    for k in range(NLIMBS):
        ref[k, :] = val[k]


def _fp_mul_kernel(a_ref, b_ref, out_ref):
    """One [16, LANE_BLOCK] tile: out = a * b mod P (relaxed form)."""
    _write16(out_ref, _k_mul(_read16(a_ref), _read16(b_ref)))


# ---------------------------------------------------------------------------
# wrappers: [B, 16] graph layout <-> [16, B] kernel tiles
# ---------------------------------------------------------------------------

def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def fp_mul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``[B, 16] x [B, 16] -> [B, 16]`` F_P multiply via the Pallas
    kernel; bit-identical to ``bigint.FP.mul`` (relaxed outputs)."""
    return _ew(_fp_mul_kernel, [a, b], interpret=interpret)


# operand layout of the ladder kernels
STRAUSS_OPS = 4  # ±G, ±lam*G, ±R, ±lam*R


# ---------------------------------------------------------------------------
# self-gathering ladder kernel (round-4 v2): the per-window table
# lookups move INSIDE the kernel as one-hot selects, so the XLA
# pre-gather/sign-fold/pack stage (~150 dispatches and two [W, 64, B]
# operand arrays — 280 MB per 16k batch — re-uploaded per call)
# disappears entirely.  Fixed-base operands (±G, ±lam*G) select from
# trace-time scalar constants; variable-base operands (±R, ±lam*R)
# select rows of the R-table refs, which stay VMEM-resident across the
# whole window walk (their index map is constant in w).  Digits arrive
# MSD-first as one tiny [W, 8, B] array; signs as [8, B].
# ---------------------------------------------------------------------------


def _k_onehot_const(dig, tab_rows, xp=jnp):
    """Per-lane lookup of a 16-entry x 16-limb CONSTANT table by digit
    vector: limbs[k] = sum_d (dig == d) * tab[d][k].  Entry 0 of every
    table is the zero row, so the d = 0 term is skipped."""
    out = []
    oh = [(dig == xp.uint32(d)).astype(xp.uint32) for d in range(1, 16)]
    for k in range(NLIMBS):
        s = xp.zeros_like(dig)
        for d in range(1, 16):
            c = tab_rows[d][k]
            if c:
                s = s + oh[d - 1] * xp.uint32(c)
        out.append(s)
    return out


def _k_onehot_ref(dig, read_row, xp=jnp):
    """Same, for a per-row table in a ref: ``read_row(d, k)`` yields the
    [B]-vector of limb k of entry d."""
    oh = [(dig == xp.uint32(d)).astype(xp.uint32) for d in range(1, 16)]
    out = []
    for k in range(NLIMBS):
        s = xp.zeros_like(dig)
        for d in range(1, 16):
            s = s + oh[d - 1] * read_row(d, k)
        out.append(s)
    return out


@functools.lru_cache(maxsize=1)
def _strauss_tab_kernel():
    # G/lam*G affine tables as trace-time int constants (entry 0 zero)
    from eges_tpu.ops.ec import _g_lam_table16, _g_table16

    tgx, tgy = _g_table16()
    tlx, _ = _g_lam_table16()
    gx_rows = tuple(tuple(int(v) for v in row) for row in tgx)
    gy_rows = tuple(tuple(int(v) for v in row) for row in tgy)
    lx_rows = tuple(tuple(int(v) for v in row) for row in tlx)

    def kernel(dig_ref, neg_ref, trx_ref, tlrx_ref, try_ref,
               ox_ref, oy_ref, oz_ref):
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            zero = jnp.zeros((LANE_BLOCK,), jnp.uint32)
            one = jnp.ones((LANE_BLOCK,), jnp.uint32)
            for k in range(NLIMBS):
                ox_ref[k, :] = zero
                oy_ref[k, :] = one if k == 0 else zero
                oz_ref[k, :] = zero

        X, Y, Z = _read16(ox_ref), _read16(oy_ref), _read16(oz_ref)
        for _ in range(4):
            X, Y, Z = _k_jac_double(X, Y, Z)
        for t in range(STRAUSS_OPS):
            dig = dig_ref[0, t, :]
            if t == 0:
                px = _k_onehot_const(dig, gx_rows)
                py = _k_onehot_const(dig, gy_rows)
            elif t == 1:
                px = _k_onehot_const(dig, lx_rows)
                py = _k_onehot_const(dig, gy_rows)
            else:
                xref = trx_ref if t == 2 else tlrx_ref
                px = _k_onehot_ref(dig, lambda d, k: xref[16 * d + k, :])
                py = _k_onehot_ref(dig, lambda d, k: try_ref[16 * d + k, :])
            py = _k_select(neg_ref[t, :], _k_neg(py), py)
            nz = (dig != 0).astype(jnp.uint32)
            AX, AY, AZ = _k_jac_add_mixed(X, Y, Z, px, py)
            X = _k_select(nz, AX, X)
            Y = _k_select(nz, AY, Y)
            Z = _k_select(nz, AZ, Z)
        _write16(ox_ref, X)
        _write16(oy_ref, Y)
        _write16(oz_ref, Z)

    return kernel


def strauss_tab(dig: jnp.ndarray, neg: jnp.ndarray, trx: jnp.ndarray,
                tlrx: jnp.ndarray, try_: jnp.ndarray, batch: int, *,
                interpret: bool | None = None):
    """Self-gathering ladder: ``dig [W, 8, Bpad]`` (rows 0-3: window
    digits of g1/g2/r1/r2, MSD-first), ``neg [8, Bpad]`` (rows 0-3:
    half-scalar signs), ``trx/tlrx/try_ [256, Bpad]`` (R / lam*R x and
    shared y affine tables, row ``16*d + k`` = limb k of entry d).
    Returns Jacobian ``(X, Y, Z)`` each ``[batch, 16]``."""
    if rows8_enabled():
        return strauss_tab_rows8(dig, neg, trx, tlrx, try_, batch,
                                 interpret=interpret)
    if interpret is None:
        interpret = _default_interpret()
    W, _, wide = dig.shape
    nb = wide // LANE_BLOCK
    outs = pl.pallas_call(
        _strauss_tab_kernel(),
        out_shape=tuple(jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32)
                        for _ in range(3)),
        grid=(nb, W),
        in_specs=[
            pl.BlockSpec((1, 8, LANE_BLOCK), lambda b, w: (w, 0, b)),
            pl.BlockSpec((8, LANE_BLOCK), lambda b, w: (0, b)),
            pl.BlockSpec((16 * NLIMBS, LANE_BLOCK), lambda b, w: (0, b)),
            pl.BlockSpec((16 * NLIMBS, LANE_BLOCK), lambda b, w: (0, b)),
            pl.BlockSpec((16 * NLIMBS, LANE_BLOCK), lambda b, w: (0, b)),
        ],
        out_specs=tuple(
            pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, w: (0, b))
            for _ in range(3)),
        interpret=interpret,
    )(dig, neg, trx, tlrx, try_)
    return tuple(o.T[:batch] for o in outs)


def strauss_tab_np(dig: np.ndarray, neg: np.ndarray, trx: np.ndarray,
                   tlrx: np.ndarray, try_: np.ndarray):
    """Numpy twin of the self-gathering ladder kernel's math."""
    from eges_tpu.ops.ec import _g_lam_table16, _g_table16

    tgx, tgy = _g_table16()
    tlx, _ = _g_lam_table16()
    gx_rows = tuple(tuple(int(v) for v in row) for row in tgx)
    gy_rows = tuple(tuple(int(v) for v in row) for row in tgy)
    lx_rows = tuple(tuple(int(v) for v in row) for row in tlx)
    W, _, wide = dig.shape
    X = [np.zeros(wide, np.uint32) for _ in range(NLIMBS)]
    Y = [np.zeros(wide, np.uint32) for _ in range(NLIMBS)]
    Y[0] = np.ones(wide, np.uint32)
    Z = [np.zeros(wide, np.uint32) for _ in range(NLIMBS)]
    for w in range(W):
        for _ in range(4):
            X, Y, Z = _k_jac_double(X, Y, Z, np)
        for t in range(STRAUSS_OPS):
            d = dig[w, t, :]
            if t == 0:
                px = _k_onehot_const(d, gx_rows, np)
                py = _k_onehot_const(d, gy_rows, np)
            elif t == 1:
                px = _k_onehot_const(d, lx_rows, np)
                py = _k_onehot_const(d, gy_rows, np)
            else:
                xt = trx if t == 2 else tlrx
                px = _k_onehot_ref(d, lambda e, k: xt[16 * e + k, :], np)
                py = _k_onehot_ref(d, lambda e, k: try_[16 * e + k, :], np)
            py = _k_select(neg[t, :], _k_neg(py, np), py, np)
            nz = (d != 0).astype(np.uint32)
            AX, AY, AZ = _k_jac_add_mixed(X, Y, Z, px, py, np)
            X = _k_select(nz, AX, X, np)
            Y = _k_select(nz, AY, Y, np)
            Z = _k_select(nz, AZ, Z, np)
    return X, Y, Z


# ---------------------------------------------------------------------------
# streamed windowed-pow kernel: a^e for a constant exponent, one launch.
# Covers the three remaining launch-heavy loops of the recover graph —
# FP.sqrt (e = (P+1)/4), FP inverse (P-2) and FN inverse (N-2): each is
# a 256-bit square-and-multiply that the XLA path runs as a rolled
# fori_loop of tiny ops (~2k launches per pow on this backend).  Here
# the grid's last dim walks 64 4-bit windows; the per-row power table
# a^0..a^15 (a^0 = 1, so digit 0 needs no conditional) is built once
# per batch block into VMEM scratch at w == 0, and the window digit —
# a compile-time constant — arrives as a tiny one-hot block shared by
# every batch block.
# ---------------------------------------------------------------------------

POW_WINDOWS = 64


def _make_pow_kernel(mul_fn):
    def kernel(sel_ref, a_ref, o_ref, tab_ref):
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            A = _read16(a_ref)
            one0 = jnp.ones_like(A[0])
            zero = jnp.zeros_like(A[0])
            for k in range(NLIMBS):
                tab_ref[k, :] = one0 if k == 0 else zero        # a^0 = 1
                tab_ref[NLIMBS + k, :] = A[k]                   # a^1
                o_ref[k, :] = one0 if k == 0 else zero          # acc = 1
            cur = A
            for e in range(2, 16):
                cur = mul_fn(cur, A)
                for k in range(NLIMBS):
                    tab_ref[NLIMBS * e + k, :] = cur[k]

        acc = _read16(o_ref)
        for _ in range(4):
            acc = mul_fn(acc, acc)
        sel = [sel_ref[0, e, :] for e in range(16)]
        op = []
        for k in range(NLIMBS):
            s = sel[0] * tab_ref[k, :]
            for e in range(1, 16):
                s = s + sel[e] * tab_ref[NLIMBS * e + k, :]
            op.append(s)
        acc = mul_fn(acc, op)
        _write16(o_ref, acc)

    return kernel


@functools.lru_cache(maxsize=2)
def _pow_kernel_for(modulus: str):
    # lazy: _k_fn_mul is defined in the order-N section below
    return _make_pow_kernel(_k_mul if modulus == "p" else _k_fn_mul)


@functools.lru_cache(maxsize=None)
def _pow_onehot(e: int) -> np.ndarray:
    """[64, 16, LANE_BLOCK] u32 one-hot of e's 4-bit digits, MSD first."""
    sel = np.zeros((POW_WINDOWS, 16, LANE_BLOCK), np.uint32)
    for w in range(POW_WINDOWS):
        d = (e >> (4 * (POW_WINDOWS - 1 - w))) & 0xF
        sel[w, d, :] = 1
    return sel


def pow_mod_pallas(a: jnp.ndarray, e: int, modulus: str, *,
                   interpret: bool | None = None) -> jnp.ndarray:
    """``[B, 16] -> [B, 16]``: per-row ``a^e`` mod P (relaxed) or mod N
    (canonical), matching ``FieldP.pow_const`` / ``OrderN.pow_const``
    outputs up to the field's representation contract."""
    from jax.experimental.pallas import tpu as pltpu

    if rows8_enabled():
        return pow_mod_rows8(a, e, modulus, interpret=interpret)
    if interpret is None:
        interpret = _default_interpret()
    assert e.bit_length() <= 4 * POW_WINDOWS
    B = a.shape[0]
    pad = (-B) % LANE_BLOCK
    at = jnp.pad(a, ((0, pad), (0, 0))).T
    wide = at.shape[1]
    sel = jnp.asarray(_pow_onehot(e))
    out = pl.pallas_call(
        _pow_kernel_for(modulus),
        out_shape=jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32),
        grid=(wide // LANE_BLOCK, POW_WINDOWS),
        in_specs=[
            pl.BlockSpec((1, 16, LANE_BLOCK), lambda b, w: (w, 0, 0)),
            pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, w: (0, b)),
        ],
        out_specs=pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, w: (0, b)),
        scratch_shapes=[pltpu.VMEM((16 * NLIMBS, LANE_BLOCK), jnp.uint32)],
        interpret=interpret,
    )(sel, at)
    return out.T[:B]


def pow_mod_np(a: np.ndarray, e: int, modulus: str) -> np.ndarray:
    """Numpy twin of the pow kernel's math for differential tests."""
    mul = _k_mul if modulus == "p" else _k_fn_mul
    A = [a[:, k].copy() for k in range(NLIMBS)]
    one0 = np.ones_like(A[0])
    zero = np.zeros_like(A[0])
    tab = [[one0 if k == 0 else zero for k in range(NLIMBS)], A]
    cur = A
    for _ in range(2, 16):
        cur = mul(cur, A, np)
        tab.append(cur)
    acc = [one0 if k == 0 else zero for k in range(NLIMBS)]
    for w in range(POW_WINDOWS):
        d = (e >> (4 * (POW_WINDOWS - 1 - w))) & 0xF
        for _ in range(4):
            acc = mul(acc, acc, np)
        acc = mul(acc, tab[d], np)
    return np.stack(acc, axis=-1)


# ---------------------------------------------------------------------------
# table-build kernel: entries 2..15 of the per-row variable-base window
# table (d*R).  The graph form is a lax.scan of 14 mixed adds — the
# last multi-thousand-launch loop on the fused path.  Grid walks the
# entries; the running point lives in VMEM scratch and each step's
# result lands in that entry's output block.
# ---------------------------------------------------------------------------

def _table_kernel(px_ref, py_ref, ox_ref, oy_ref, oz_ref, cur_ref):
    d = pl.program_id(1)
    px, py = _read16(px_ref), _read16(py_ref)

    @pl.when(d == 0)
    def _init():  # cur = 1*R (affine lifted to Jacobian)
        one0 = jnp.ones((LANE_BLOCK,), jnp.uint32)
        zero = jnp.zeros((LANE_BLOCK,), jnp.uint32)
        for k in range(NLIMBS):
            cur_ref[k, :] = px[k]
            cur_ref[NLIMBS + k, :] = py[k]
            cur_ref[2 * NLIMBS + k, :] = one0 if k == 0 else zero

    X = [cur_ref[k, :] for k in range(NLIMBS)]
    Y = [cur_ref[NLIMBS + k, :] for k in range(NLIMBS)]
    Z = [cur_ref[2 * NLIMBS + k, :] for k in range(NLIMBS)]
    X, Y, Z = _k_jac_add_mixed(X, Y, Z, px, py)
    for k in range(NLIMBS):
        cur_ref[k, :] = X[k]
        cur_ref[NLIMBS + k, :] = Y[k]
        cur_ref[2 * NLIMBS + k, :] = Z[k]
    _write16(ox_ref, X)
    _write16(oy_ref, Y)
    _write16(oz_ref, Z)


def point_table_pallas(px: jnp.ndarray, py: jnp.ndarray, *,
                       interpret: bool | None = None):
    """``[B, 16]`` affine R -> Jacobian entries ``d*R`` for d in 2..15,
    each ``[14, B, 16]`` (X, Y, Z); bit-identical to the lax.scan of
    ``ec.jac_add_mixed`` in ``_build_point_table``."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _default_interpret()
    B = px.shape[0]
    pad = (-B) % LANE_BLOCK
    pxt = jnp.pad(px, ((0, pad), (0, 0))).T
    pyt = jnp.pad(py, ((0, pad), (0, 0))).T
    wide = pxt.shape[1]
    outs = pl.pallas_call(
        _table_kernel,
        out_shape=tuple(jax.ShapeDtypeStruct((14 * NLIMBS, wide),
                                             jnp.uint32) for _ in range(3)),
        grid=(wide // LANE_BLOCK, 14),
        in_specs=[pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, d: (0, b)),
                  pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, d: (0, b))],
        out_specs=tuple(
            pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda b, d: (d, b))
            for _ in range(3)),
        scratch_shapes=[pltpu.VMEM((3 * NLIMBS, LANE_BLOCK), jnp.uint32)],
        interpret=interpret,
    )(pxt, pyt)
    # [14*16, wide] -> [14, B, 16]
    return tuple(o.reshape(14, NLIMBS, wide).transpose(0, 2, 1)[:, :B]
                 for o in outs)


def point_table_np(px: np.ndarray, py: np.ndarray):
    """Numpy twin of the table kernel."""
    B = px.shape[0]
    pxl = [px[:, k].copy() for k in range(NLIMBS)]
    pyl = [py[:, k].copy() for k in range(NLIMBS)]
    X, Y = list(pxl), list(pyl)
    Z = [np.ones(B, np.uint32) if k == 0 else np.zeros(B, np.uint32)
         for k in range(NLIMBS)]
    outs = []
    for _ in range(14):
        X, Y, Z = _k_jac_add_mixed(X, Y, Z, pxl, pyl, np)
        outs.append((np.stack(X, -1), np.stack(Y, -1), np.stack(Z, -1)))
    return (np.stack([o[0] for o in outs]), np.stack([o[1] for o in outs]),
            np.stack([o[2] for o in outs]))


# ---------------------------------------------------------------------------
# keccak-f[1600] kernel: the address-derivation tail of ecrecover
# (keccak256(x||y)[12:]).  The XLA form is already a rolled 24-round
# fori_loop (~1.5k executed ops per batch, ops/keccak_tpu.py); once the
# ladder and pow loops are fused that tail becomes a visible share of
# the launch bill, so the single-block permutation gets a kernel too.
# In-kernel the 25x2 u32 state is a Python list of [B]-vectors: every
# theta/rho/pi/chi index is a compile-time constant, so there are no
# gathers at all — just vector xor/and/shift.  Rounds unroll at trace
# time (24 x ~150 vector ops: well inside Mosaic's comfort zone).
# ---------------------------------------------------------------------------

_KECCAK_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_KECCAK_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2],
               [62, 6, 43, 15, 61], [28, 55, 25, 21, 56],
               [27, 20, 39, 8, 14]]  # [x][y], lane l = x + 5y


def _k_rot64(lo, hi, r: int, xp=jnp):
    r %= 64
    if r == 0:
        return lo, hi
    if r >= 32:
        lo, hi = hi, lo
        r -= 32
        if r == 0:
            return lo, hi
    rs, inv = xp.uint32(r), xp.uint32(32 - r)
    return ((lo << rs) | (hi >> inv)), ((hi << rs) | (lo >> inv))


def _k_keccak_words(w, xp=jnp):
    """34 LE u32 words (one padded 136-byte block) -> 8 digest words.
    State lanes as (lo, hi) u32 pairs, all indices constant."""
    zero = xp.zeros_like(w[0])
    lo = [w[2 * l] for l in range(17)] + [zero] * 8
    hi = [w[2 * l + 1] for l in range(17)] + [zero] * 8
    for rnd in range(24):
        # theta
        clo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20]
               for x in range(5)]
        chi_ = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20]
                for x in range(5)]
        for x in range(5):
            rl, rh = _k_rot64(clo[(x + 1) % 5], chi_[(x + 1) % 5], 1, xp)
            dlo, dhi = clo[(x + 4) % 5] ^ rl, chi_[(x + 4) % 5] ^ rh
            for y in range(5):
                lo[x + 5 * y] = lo[x + 5 * y] ^ dlo
                hi[x + 5 * y] = hi[x + 5 * y] ^ dhi
        # rho + pi
        blo, bhi = [None] * 25, [None] * 25
        for x in range(5):
            for y in range(5):
                dl = y + 5 * ((2 * x + 3 * y) % 5)
                blo[dl], bhi[dl] = _k_rot64(lo[x + 5 * y], hi[x + 5 * y],
                                            _KECCAK_ROT[x][y], xp)
        # chi
        for y in range(5):
            row_l = [blo[x + 5 * y] for x in range(5)]
            row_h = [bhi[x + 5 * y] for x in range(5)]
            for x in range(5):
                lo[x + 5 * y] = row_l[x] ^ (~row_l[(x + 1) % 5]
                                            & row_l[(x + 2) % 5])
                hi[x + 5 * y] = row_h[x] ^ (~row_h[(x + 1) % 5]
                                            & row_h[(x + 2) % 5])
        # iota
        lo[0] = lo[0] ^ xp.uint32(_KECCAK_RC[rnd] & 0xFFFFFFFF)
        hi[0] = hi[0] ^ xp.uint32(_KECCAK_RC[rnd] >> 32)
    return [lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], lo[3], hi[3]]


def _keccak_kernel(w_ref, o_ref):
    out = _k_keccak_words([w_ref[k, :] for k in range(34)])
    for k in range(8):
        o_ref[k, :] = out[k]


def keccak_block_pallas(words: jnp.ndarray, *,
                        interpret: bool | None = None) -> jnp.ndarray:
    """``[B, 34]`` LE u32 words of one padded block -> ``[B, 8]``
    digest words (matches keccak_tpu's squeeze order)."""
    B = words.shape[0]
    pad = (-B) % LANE_BLOCK
    wt = jnp.pad(words, ((0, pad), (0, 0))).T  # [34, wide]
    return keccak_rows_pallas(wt, interpret=interpret).T[:B]


# ---------------------------------------------------------------------------
# rows8 experiment (EGES_TPU_ROWS8=1): (8, 128)-packed limb rows for
# the two compute-heaviest kernels.  The default layout keeps each limb
# as a [LANE]-wide 1-D vector, which Mosaic lays out (1, LANE) — one of
# eight sublanes live, so the VPU idles 7/8 of its datapath on every
# op.  Here one batch block is 1024 rows shaped (8, 128): a value is 16
# limbs x one full (8, 128) vreg each, array row ``limb*8 + sublane``.
# The ``_k_*`` math is shape-agnostic, so these kernels only change the
# ref plumbing.  Gated off by default until the on-chip A/B (the bench
# correctness gate runs before any timing is trusted).  Validation
# story: the re-lay index contract is pinned by
# test_rows8_layout_roundtrip; the kernel bodies reuse the twin-tested
# _k_* math; interpret mode is NOT a viable differential here (the
# (8,128)-block flat graphs take >15 min to compile on the 1-core
# host), so end-to-end proof is the hardware gate, as with LANE_BLOCK.
# ---------------------------------------------------------------------------

ROWS8_BLOCK = 1024  # rows per grid step: 8 sublanes x 128 lanes


def rows8_enabled() -> bool:
    if os.environ.get("EGES_TPU_ROWS8", "") != "1":
        return False
    if LANE_BLOCK % ROWS8_BLOCK:
        raise ValueError(
            "EGES_TPU_ROWS8=1 requires EGES_TPU_LANE_BLOCK to be a "
            f"multiple of {ROWS8_BLOCK} (got {LANE_BLOCK}) so every "
            "padded batch width re-lays into (8, 128) tiles")
    return True


def _r8_read(ref, k: int):
    """Limb k of a (1, 128, 128) value block -> (8, 128)."""
    return ref[0, 8 * k:8 * (k + 1), :]


def _r8_read16(ref):
    return [_r8_read(ref, k) for k in range(NLIMBS)]


def _r8_write16(ref, val):
    for k in range(NLIMBS):
        ref[0, 8 * k:8 * (k + 1), :] = val[k]


def _to_rows8(a: jnp.ndarray) -> jnp.ndarray:
    """``[B, 16]`` (B a ROWS8_BLOCK multiple) -> ``[nb, 128, 128]``
    with row ``limb*8 + sublane``; batch b = block*1024 + s*128 + l."""
    B = a.shape[0]
    nb = B // ROWS8_BLOCK
    return (a.T.reshape(NLIMBS, nb, 8, 128).transpose(1, 0, 2, 3)
            .reshape(nb, NLIMBS * 8, 128))


def _from_rows8(a: jnp.ndarray, B: int) -> jnp.ndarray:
    nb = a.shape[0]
    return (a.reshape(nb, NLIMBS, 8, 128).transpose(1, 0, 2, 3)
            .reshape(NLIMBS, nb * ROWS8_BLOCK).T[:B])


def _pad_rows8(a: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    B = a.shape[0]
    pad = (-B) % ROWS8_BLOCK
    return jnp.pad(a, ((0, pad), (0, 0))), B


@functools.lru_cache(maxsize=2)
def _pow_kernel_rows8(modulus: str):
    mul_fn = _k_mul if modulus == "p" else _k_fn_mul

    def kernel(sel_ref, a_ref, o_ref, tab_ref):
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            A = _r8_read16(a_ref)
            one0 = jnp.ones_like(A[0])
            zero = jnp.zeros_like(A[0])
            for k in range(NLIMBS):
                tab_ref[8 * k:8 * (k + 1), :] = one0 if k == 0 else zero
                tab_ref[8 * (NLIMBS + k):8 * (NLIMBS + k) + 8, :] = A[k]
                o_ref[0, 8 * k:8 * (k + 1), :] = one0 if k == 0 else zero
            cur = A
            for e in range(2, 16):
                cur = mul_fn(cur, A)
                for k in range(NLIMBS):
                    r0 = 8 * (NLIMBS * e + k)
                    tab_ref[r0:r0 + 8, :] = cur[k]

        acc = _r8_read16(o_ref)
        for _ in range(4):
            acc = mul_fn(acc, acc)
        sel = [sel_ref[0, e, :] for e in range(16)]  # (128,) rows
        op = []
        for k in range(NLIMBS):
            s = sel[0] * tab_ref[8 * k:8 * (k + 1), :]
            for e in range(1, 16):
                r0 = 8 * (NLIMBS * e + k)
                s = s + sel[e] * tab_ref[r0:r0 + 8, :]
            op.append(s)
        acc = mul_fn(acc, op)
        _r8_write16(o_ref, acc)

    return kernel


def pow_mod_rows8(a: jnp.ndarray, e: int, modulus: str, *,
                  interpret: bool | None = None) -> jnp.ndarray:
    """rows8 twin of :func:`pow_mod_pallas` — same contract."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _default_interpret()
    assert e.bit_length() <= 4 * POW_WINDOWS
    ap, B = _pad_rows8(a)
    at = _to_rows8(ap)
    nb = at.shape[0]
    sel = jnp.asarray(_pow_onehot(e)[:, :, :128])
    out = pl.pallas_call(
        _pow_kernel_rows8(modulus),
        out_shape=jax.ShapeDtypeStruct((nb, NLIMBS * 8, 128), jnp.uint32),
        grid=(nb, POW_WINDOWS),
        in_specs=[
            pl.BlockSpec((1, 16, 128), lambda b, w: (w, 0, 0)),
            pl.BlockSpec((1, NLIMBS * 8, 128), lambda b, w: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, NLIMBS * 8, 128), lambda b, w: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((16 * NLIMBS * 8, 128), jnp.uint32)],
        interpret=interpret,
    )(sel, at)
    return _from_rows8(out, B)


@functools.lru_cache(maxsize=1)
def _strauss_tab_kernel_rows8():
    from eges_tpu.ops.ec import _g_lam_table16, _g_table16

    tgx, tgy = _g_table16()
    tlx, _ = _g_lam_table16()
    gx_rows = tuple(tuple(int(v) for v in row) for row in tgx)
    gy_rows = tuple(tuple(int(v) for v in row) for row in tgy)
    lx_rows = tuple(tuple(int(v) for v in row) for row in tlx)

    def kernel(dig_ref, neg_ref, trx_ref, tlrx_ref, try_ref,
               ox_ref, oy_ref, oz_ref):
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            zero = jnp.zeros((8, 128), jnp.uint32)
            one = jnp.ones((8, 128), jnp.uint32)
            for k in range(NLIMBS):
                ox_ref[0, 8 * k:8 * k + 8, :] = zero
                oy_ref[0, 8 * k:8 * k + 8, :] = one if k == 0 else zero
                oz_ref[0, 8 * k:8 * k + 8, :] = zero

        X = _r8_read16(ox_ref)
        Y = _r8_read16(oy_ref)
        Z = _r8_read16(oz_ref)
        for _ in range(4):
            X, Y, Z = _k_jac_double(X, Y, Z)
        for t in range(STRAUSS_OPS):
            dig = dig_ref[0, 0, 8 * t:8 * t + 8, :]
            if t == 0:
                px = _k_onehot_const(dig, gx_rows)
                py = _k_onehot_const(dig, gy_rows)
            elif t == 1:
                px = _k_onehot_const(dig, lx_rows)
                py = _k_onehot_const(dig, gy_rows)
            else:
                xref = trx_ref if t == 2 else tlrx_ref

                def rr(d, k, ref=xref):
                    r0 = 8 * (16 * d + k)
                    return ref[0, r0:r0 + 8, :]

                px = _k_onehot_ref(dig, rr)
                py = _k_onehot_ref(
                    dig, lambda d, k: try_ref[0, 8 * (16 * d + k):
                                              8 * (16 * d + k) + 8, :])
            py = _k_select(neg_ref[0, 8 * t:8 * t + 8, :], _k_neg(py), py)
            nz = (dig != 0).astype(jnp.uint32)
            AX, AY, AZ = _k_jac_add_mixed(X, Y, Z, px, py)
            X = _k_select(nz, AX, X)
            Y = _k_select(nz, AY, Y)
            Z = _k_select(nz, AZ, Z)
        _r8_write16(ox_ref, X)
        _r8_write16(oy_ref, Y)
        _r8_write16(oz_ref, Z)

    return kernel


def strauss_tab_rows8(dig: jnp.ndarray, neg: jnp.ndarray, trx: jnp.ndarray,
                      tlrx: jnp.ndarray, try_: jnp.ndarray, batch: int, *,
                      interpret: bool | None = None):
    """rows8 twin of :func:`strauss_tab`: same [W, 8, Bpad]/[8, Bpad]/
    [256, Bpad] inputs (Bpad a ROWS8_BLOCK multiple), re-laid here."""
    if interpret is None:
        interpret = _default_interpret()
    W, _, wide = dig.shape
    nb = wide // ROWS8_BLOCK

    def lay(rows):  # [R, wide] -> [nb, R*8, 128], row r*8 + sublane
        R = rows.shape[0]
        return (rows.reshape(R, nb, 8, 128).transpose(1, 0, 2, 3)
                .reshape(nb, R * 8, 128))

    digl = (dig.reshape(W, 8, nb, 8, 128).transpose(2, 0, 1, 3, 4)
            .reshape(nb, W, 64, 128))
    negl = lay(neg)
    outs = pl.pallas_call(
        _strauss_tab_kernel_rows8(),
        out_shape=tuple(
            jax.ShapeDtypeStruct((nb, NLIMBS * 8, 128), jnp.uint32)
            for _ in range(3)),
        grid=(nb, W),
        in_specs=[
            pl.BlockSpec((1, 1, 64, 128), lambda b, w: (b, w, 0, 0)),
            pl.BlockSpec((1, 64, 128), lambda b, w: (b, 0, 0)),
            pl.BlockSpec((1, 16 * NLIMBS * 8, 128), lambda b, w: (b, 0, 0)),
            pl.BlockSpec((1, 16 * NLIMBS * 8, 128), lambda b, w: (b, 0, 0)),
            pl.BlockSpec((1, 16 * NLIMBS * 8, 128), lambda b, w: (b, 0, 0)),
        ],
        out_specs=tuple(
            pl.BlockSpec((1, NLIMBS * 8, 128), lambda b, w: (b, 0, 0))
            for _ in range(3)),
        interpret=interpret,
    )(digl, negl, lay(trx), lay(tlrx), lay(try_))
    return tuple(_from_rows8(o, batch) for o in outs)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def pallas_enabled() -> bool:
    """Historical opt-in: ``EGES_TPU_PALLAS=1`` routes ``FP.mul`` on 2-D
    batches through the per-multiply kernel (``bigint.FieldP.mul``)."""
    return os.environ.get("EGES_TPU_PALLAS", "") == "1"


@functools.lru_cache(maxsize=1)
def ladder_kernels_enabled() -> bool:
    """Route the recover pipeline through the fused kernels (the
    composite stage kernels, glv_digits, strauss_tab, the pow ladders,
    the R-table build, the keccak tail, the one-launch glue ops) — TPU
    backend only (interpret mode would lower each kernel back to
    per-block HLO and re-explode the CPU graph).

    DEFAULT ON for the TPU backend (the un-fused graph executes each
    HLO op as its own dispatch there, so per-launch overhead dominates
    it).  ``EGES_TPU_PALLAS=off`` (or
    ``0``) opts out; ``ladder`` forces the historical explicit opt-in;
    ``1`` selects the per-multiply hook instead (see
    :func:`pallas_enabled`)."""
    val = os.environ.get("EGES_TPU_PALLAS", "")
    if val in ("off", "0", "1"):
        return False
    return (val in ("", "ladder")
            and jax.default_backend() == "tpu")


# ---------------------------------------------------------------------------
# order-N (scalar field) multiply kernel: mirrors OrderN.mul =
# _red_cols(big_mul_cols(a, b)) — the mod-N arithmetic of the scalar
# recovery prelude (u1/u2, GLV decomposition)
# ---------------------------------------------------------------------------

from eges_tpu.ops.bigint import N as _ORDER_N  # noqa: E402

_N_LIMBS_C = [int(v) for v in int_to_limbs(_ORDER_N)]
_N_DELTA = (1 << 256) - _ORDER_N
_N_DELTA_LIMBS = [int(v)
                  for v in int_to_limbs(_N_DELTA,
                                        (_N_DELTA.bit_length() + 15) // 16)]


def _k_carry(cols, n_out, xp=jnp):
    """Generic carry chain over small (< 2^31) columns -> n_out limbs."""
    mask = xp.uint32(MASK)
    out = []
    c = xp.zeros_like(cols[0])
    for k in range(len(cols)):
        t = cols[k] + c
        out.append(t & mask)
        c = t >> 16
    while len(out) < n_out:
        out.append(c & mask)
        c = c >> 16
    return out[:n_out]


def _k_mul_cols(a, b_const, xp=jnp):
    """Uncarried schoolbook columns of (limb list a) x (Python-int limb
    constants b_const); mirrors ``big_mul_cols``."""
    mask = xp.uint32(MASK)
    zero = xp.zeros_like(a[0])
    cols = [zero] * (len(a) + len(b_const))
    for i, ai in enumerate(a):
        for j, bj in enumerate(b_const):
            p = ai * xp.uint32(bj)
            cols[i + j] = cols[i + j] + (p & mask)
            cols[i + j + 1] = cols[i + j + 1] + (p >> 16)
    return cols


def _k_mul_cols_vv(a, b, xp=jnp):
    """Uncarried schoolbook columns, both operands limb lists."""
    mask = xp.uint32(MASK)
    zero = xp.zeros_like(a[0])
    cols = [zero] * (len(a) + len(b))
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            p = ai * bj
            cols[i + j] = cols[i + j] + (p & mask)
            cols[i + j + 1] = cols[i + j + 1] + (p >> 16)
    return cols


def _k_sub_const_chain(a, m_limbs, xp=jnp):
    """Borrow-chain ``a - const``: returns (diff_limbs, borrow_flag);
    borrow == 1 iff a < const.  The one borrow chain shared by the
    conditional subtracts and the range checks."""
    mask = xp.uint32(MASK)
    out = []
    borrow = xp.zeros_like(a[0])
    for k in range(16):
        t = a[k] + xp.uint32(1 << 16) - xp.uint32(m_limbs[k]) - borrow
        out.append(t & mask)
        borrow = xp.uint32(1) - (t >> 16)
    return out, borrow


def _k_cond_sub(a, m_limbs, xp=jnp):
    """One conditional subtract of the constant ``m_limbs``; shared by
    the mod-N and mod-P variants."""
    out, borrow = _k_sub_const_chain(a, m_limbs, xp)
    return _k_select(borrow, a, out, xp)


def _k_cond_sub_n(a, xp=jnp):
    return _k_cond_sub(a, _N_LIMBS_C, xp)


def _k_fn_mul(a, b, xp=jnp):
    """Canonical mod-N product; mirrors ``OrderN.mul`` fold-for-fold
    (three delta folds 32 -> 26 -> 20 -> 16+eps, then two top-limb
    folds and two conditional subtracts)."""
    return _k_fn_red_cols(_k_mul_cols_vv(a, b, xp), xp)


def _fn_mul_kernel(a_ref, b_ref, out_ref):
    """One [16, LANE_BLOCK] tile: out = a * b mod N (canonical)."""
    _write16(out_ref, _k_fn_mul(_read16(a_ref), _read16(b_ref)))


def fn_mul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``[B, 16] x [B, 16] -> [B, 16]`` mod-N multiply via the Pallas
    kernel; bit-identical to ``bigint.FN.mul``."""
    return _ew(_fn_mul_kernel, [a, b], interpret=interpret)


# ---------------------------------------------------------------------------
# glue kernels: every remaining field op of the recover pipeline.
#
# Round-4 on-chip census: with the LOOPS fused,
# the recover graph still executed as ~3.8k XLA fusions — carry chains
# of the scalar prelude, the GLV split, the y-recovery, the table
# normalization and the affine tail — and on this backend each fusion
# is its own ~0.1 ms dispatch, so the glue cost ~25x the kernels' own
# arithmetic (65 ms of kernel time inside a 1.9 s batch at 1024 rows).
# Each helper below turns one field-op call site into ONE launch; the
# in-kernel math reuses the ``_k_*`` library above bit-for-bit, so the
# fused and plain paths stay differential-testable against each other.
# ---------------------------------------------------------------------------

def _k_cond_sub_p(a, xp=jnp):
    """In-kernel twin of ``Mod._cond_sub_m`` for the field prime."""
    return _k_cond_sub(a, _P_LIMBS, xp)


def _k_fn_red_cols(cols, xp=jnp):
    """Small (< 2^31) columns, any width in (16, 32] -> canonical mod-N
    value; the reduction tail of ``_k_fn_mul`` (mirrors
    ``OrderN._red_cols`` fold-for-fold)."""
    while len(cols) > 16:
        lo = cols[:16]
        hi = _k_carry(cols[16:], len(cols) - 16 + 1, xp)
        prod = _k_mul_cols(hi, _N_DELTA_LIMBS, xp)
        w = max(16, len(prod))
        zero = xp.zeros_like(cols[0])
        lo_w = lo + [zero] * (w - 16)
        pr_w = prod + [zero] * (w - len(prod))
        cols = [x + y for x, y in zip(lo_w, pr_w)]
    a17 = _k_carry(cols, 17, xp)
    for _ in range(2):
        top = a17[16]
        fold = _k_mul_cols([top], _N_DELTA_LIMBS, xp)[:16]
        zero = xp.zeros_like(top)
        fold = fold + [zero] * (16 - len(fold))
        a17 = _k_carry([x + y for x, y in zip(a17[:16], fold)], 17, xp)
    out = _k_cond_sub_n(a17[:16], xp)
    return _k_cond_sub_n(out, xp)


# C with cols_k = a_k + (MASK - b_k) + C_k giving a - b + (2^256 - 1) + C
# ≡ a - b + 2N (mod N): borrow-free per-limb subtraction mod N.
_FN_SUBC = (2 * _ORDER_N) - (1 << 256) + 1
_FN_SUBC_LIMBS = [int(v) for v in int_to_limbs(_FN_SUBC)]


def _k_fn_sub(a, b, xp=jnp):
    """Canonical a - b mod N (both canonical)."""
    mask = xp.uint32(MASK)
    cols = [x + (mask - y) + xp.uint32(_FN_SUBC_LIMBS[k])
            for k, (x, y) in enumerate(zip(a, b))]
    return _k_fn_red_cols(cols, xp)


def _k_is_zero(a, xp=jnp):
    z = a[0] == 0
    for k in range(1, 16):
        z = z & (a[k] == 0)
    return z.astype(xp.uint32)


def _k_fn_neg(a, xp=jnp):
    """Canonical -a mod N (0 -> 0)."""
    out = _k_fn_sub([xp.zeros_like(v) for v in a], a, xp)
    return _k_select(_k_is_zero(a, xp), [xp.zeros_like(v) for v in a],
                     out, xp)


# glue kernel bodies (each one [rows, LANE_BLOCK] tile set)

def _fp_add_kernel(a_ref, b_ref, o_ref):
    _write16(o_ref, _k_add(_read16(a_ref), _read16(b_ref)))


def _fp_sub_kernel(a_ref, b_ref, o_ref):
    _write16(o_ref, _k_sub(_read16(a_ref), _read16(b_ref)))


def _fp_neg_kernel(a_ref, o_ref):
    _write16(o_ref, _k_neg(_read16(a_ref)))


def _fp_canon_kernel(a_ref, o_ref):
    _write16(o_ref, _k_cond_sub_p(_read16(a_ref)))


def _fn_sub_kernel(a_ref, b_ref, o_ref):
    _write16(o_ref, _k_fn_sub(_read16(a_ref), _read16(b_ref)))


def _fn_neg_kernel(a_ref, o_ref):
    _write16(o_ref, _k_fn_neg(_read16(a_ref)))


def _fn_red17_kernel(a_ref, o_ref):
    cols = [a_ref[k, :] for k in range(17)]
    _write16(o_ref, _k_fn_red_cols(cols))


@functools.lru_cache(maxsize=4)
def _mulhi8_kernel_for(g: int):
    """Kernel: high limbs 24..31 of a 16-limb value times the 16-limb
    constant ``g`` (the GLV rounding step ``(k * g) >> 384``)."""
    g_limbs = [int(v) for v in int_to_limbs(g)]

    def kernel(a_ref, o_ref):
        cols = _k_mul_cols(_read16(a_ref), g_limbs)
        limbs = _k_carry(cols, 32)
        for k in range(8):
            o_ref[k, :] = limbs[24 + k]

    return kernel


def _rows_call(kernel, arrs, in_rows, out_rows, interpret):
    """Shared launch plumbing for the glue kernels: each operand is a
    ``[rows_i, B]`` array tiled over LANE_BLOCK batch columns."""
    wide = arrs[0].shape[-1]
    nb = wide // LANE_BLOCK
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct((r, wide), jnp.uint32)
                        for r in out_rows),
        grid=(nb,),
        in_specs=[pl.BlockSpec((r, LANE_BLOCK), lambda i: (0, i))
                  for r in in_rows],
        out_specs=tuple(pl.BlockSpec((r, LANE_BLOCK), lambda i: (0, i))
                        for r in out_rows),
        interpret=interpret,
    )(*arrs)
    return outs


def _ew(kernel, ins, out_limbs=NLIMBS, *, interpret=None):
    """Elementwise-style glue launch: ``ins`` are ``[B, rows_i]`` limb
    arrays (same B), output ``[B, out_limbs]``."""
    if interpret is None:
        interpret = _default_interpret()
    B = ins[0].shape[0]
    pad = (-B) % LANE_BLOCK
    ats = [jnp.pad(a, ((0, pad), (0, 0))).T for a in ins]
    out, = _rows_call(kernel, ats, [a.shape[1] for a in ins],
                      [out_limbs], interpret)
    return out.T[:B]


def fp_add_pallas(a, b, **kw):
    return _ew(_fp_add_kernel, [a, b], **kw)


def fp_sub_pallas(a, b, **kw):
    return _ew(_fp_sub_kernel, [a, b], **kw)


def fp_neg_pallas(a, **kw):
    return _ew(_fp_neg_kernel, [a], **kw)


def fp_canon_pallas(a, **kw):
    return _ew(_fp_canon_kernel, [a], **kw)


def fn_sub_pallas(a, b, **kw):
    return _ew(_fn_sub_kernel, [a, b], **kw)


def fn_neg_pallas(a, **kw):
    return _ew(_fn_neg_kernel, [a], **kw)


def fn_red17_pallas(a, **kw):
    """``[B, 17]`` small-column value -> canonical mod-N ``[B, 16]``."""
    return _ew(_fn_red17_kernel, [a], **kw)


def mulhi8_pallas(a, g: int, **kw):
    """``[B, 16] -> [B, 8]``: limbs 24..31 of ``a * g`` for constant g."""
    return _ew(_mulhi8_kernel_for(g), [a], out_limbs=8, **kw)


# ---------------------------------------------------------------------------
# GLV-decompose kernel (round-4 v2): both recovery scalars -> ladder
# digits + signs in ONE launch, emitted directly in the strauss_tab
# input layout.  Absorbs what the XLA graph ran as ~60 dispatches: two
# (k*g)>>384 rounding products per scalar, four mod-N muls, the k1/k2
# lattice subtractions, the sign splits (|k| < 2^140 test + negate)
# and the 33-window digit extraction/transpose/pack.
# ---------------------------------------------------------------------------

_GLV_WINDOWS = 33


def _k_glv_track(u, consts, xp=jnp):
    """One scalar's GLV split: canonical mod-N ``u`` (16 limbs) ->
    (k1_digits, neg1, k2_digits, neg2), digits MSD-first length 33.
    Mirrors ``ec._glv_decompose`` + ``_digits33`` value-for-value."""
    g1, g2, a1, a2, b1n, b2 = consts

    def mulhi8(a, g_limbs):
        limbs = _k_carry(_k_mul_cols(a, g_limbs, xp), 32, xp)
        return limbs[24:32] + [xp.zeros_like(a[0])] * 8

    def fn_mul_const(a, c_limbs):
        return _k_fn_red_cols(_k_mul_cols(a, c_limbs, xp), xp)

    c1 = mulhi8(u, g1)
    c2 = mulhi8(u, g2)
    k1 = _k_fn_sub(_k_fn_sub(u, fn_mul_const(c1, a1), xp),
                   fn_mul_const(c2, a2), xp)
    k2 = _k_fn_sub(fn_mul_const(c1, b1n), fn_mul_const(c2, b2), xp)

    def sign_split(v):
        # negative residues are detected by size: |k| < 2^140 always
        hi = v[8] >> xp.uint32(12)
        for k in range(9, 16):
            hi = hi | v[k]
        neg = (hi != 0).astype(xp.uint32)
        mag = _k_select(neg, _k_fn_neg(v, xp), v, xp)
        return mag, neg

    k1m, n1 = sign_split(k1)
    k2m, n2 = sign_split(k2)

    def digits(v):
        # MSD-first 4-bit windows of a 132-bit magnitude
        out = []
        for w in range(_GLV_WINDOWS):
            j = _GLV_WINDOWS - 1 - w           # LSD window index
            out.append((v[j // 4] >> xp.uint32(4 * (j % 4))) & xp.uint32(0xF))
        return out

    return digits(k1m), n1, digits(k2m), n2


@functools.lru_cache(maxsize=1)
def _glv_kernel():
    from eges_tpu.ops.ec import (
        _G_A1, _G_A2, _G_B1N, _G_B2, _G_G1, _G_G2,
    )

    def limbs(x):
        return tuple(int(v) for v in int_to_limbs(x))

    consts = (limbs(_G_G1), limbs(_G_G2), limbs(_G_A1), limbs(_G_A2),
              limbs(_G_B1N), limbs(_G_B2))

    def kernel(u1_ref, u2_ref, dig_ref, neg_ref):
        dg1, n1g, dg2, n2g = _k_glv_track(_read16(u1_ref), consts)
        dr1, n1r, dr2, n2r = _k_glv_track(_read16(u2_ref), consts)
        zero = jnp.zeros((LANE_BLOCK,), jnp.uint32)
        for w in range(_GLV_WINDOWS):
            dig_ref[w, 0, :] = dg1[w]
            dig_ref[w, 1, :] = dg2[w]
            dig_ref[w, 2, :] = dr1[w]
            dig_ref[w, 3, :] = dr2[w]
            for t in range(4, 8):
                dig_ref[w, t, :] = zero
        for t, n in enumerate((n1g, n2g, n1r, n2r)):
            neg_ref[t, :] = n
        for t in range(4, 8):
            neg_ref[t, :] = zero

    return kernel


def glv_digits_pallas(u1: jnp.ndarray, u2: jnp.ndarray, *,
                      interpret: bool | None = None):
    """``u1/u2 [B, 16]`` canonical mod-N scalars -> ``(dig [33, 8,
    Bpad], neg [8, Bpad])`` ready for :func:`strauss_tab`."""
    if interpret is None:
        interpret = _default_interpret()
    B = u1.shape[0]
    pad = (-B) % LANE_BLOCK
    u1t = jnp.pad(u1, ((0, pad), (0, 0))).T
    u2t = jnp.pad(u2, ((0, pad), (0, 0))).T
    wide = u1t.shape[1]
    dig, neg = pl.pallas_call(
        _glv_kernel(),
        out_shape=(jax.ShapeDtypeStruct((_GLV_WINDOWS, 8, wide), jnp.uint32),
                   jax.ShapeDtypeStruct((8, wide), jnp.uint32)),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i))] * 2,
        out_specs=(pl.BlockSpec((_GLV_WINDOWS, 8, LANE_BLOCK),
                                lambda i: (0, 0, i)),
                   pl.BlockSpec((8, LANE_BLOCK), lambda i: (0, i))),
        interpret=interpret,
    )(u1t, u2t)
    return dig, neg


def glv_digits_np(u1: np.ndarray, u2: np.ndarray):
    """Numpy twin of the GLV-decompose kernel (unpadded)."""
    from eges_tpu.ops.ec import (
        _G_A1, _G_A2, _G_B1N, _G_B2, _G_G1, _G_G2,
    )

    def limbs(x):
        return tuple(int(v) for v in int_to_limbs(x))

    consts = (limbs(_G_G1), limbs(_G_G2), limbs(_G_A1), limbs(_G_A2),
              limbs(_G_B1N), limbs(_G_B2))
    B = u1.shape[0]
    t1 = [u1[:, k].copy() for k in range(NLIMBS)]
    t2 = [u2[:, k].copy() for k in range(NLIMBS)]
    dg1, n1g, dg2, n2g = _k_glv_track(t1, consts, np)
    dr1, n1r, dr2, n2r = _k_glv_track(t2, consts, np)
    dig = np.zeros((_GLV_WINDOWS, 8, B), np.uint32)
    for w in range(_GLV_WINDOWS):
        dig[w, 0], dig[w, 1] = dg1[w], dg2[w]
        dig[w, 2], dig[w, 3] = dr1[w], dr2[w]
    neg = np.zeros((8, B), np.uint32)
    for t, n in enumerate((n1g, n2g, n1r, n2r)):
        neg[t] = n
    return dig, neg


@functools.lru_cache(maxsize=8)
def _mul_small_kernel_for(k: int):
    def kernel(a_ref, o_ref):
        _write16(o_ref, _k_mul_small(_read16(a_ref), k))

    return kernel


def fp_mul_small_pallas(a, k: int, **kw):
    return _ew(_mul_small_kernel_for(k), [a], **kw)


# ---------------------------------------------------------------------------
# recover-pipeline composite kernels (round-4 v2): the scalar prelude,
# the y-fix after sqrt, the u1/u2 scalars after the mod-N inverse, and
# the affine/keccak-prep finish — each a whole pipeline STAGE as one
# launch.  The per-op glue kernels above cut the graph from ~3.8k to
# ~640 dispatches; these composites absorb the remaining carry chains,
# range checks, parity fixes and byte packing that still ran as
# separate fusions (each its own dispatch on the device).
# ---------------------------------------------------------------------------


def _k_lt_const(a, m_limbs, xp=jnp):
    """Borrow-chain a < const flag ([B] u32 0/1); mirrors big_lt."""
    return _k_sub_const_chain(a, m_limbs, xp)[1]


def _k_unpack_be(rows, off, xp=jnp):
    """32 big-endian byte rows (u32 values < 256) starting at ``off``
    -> 16 LE 16-bit limbs; mirrors ``bigint.bytes_be_to_limbs``."""
    return [rows[off + 31 - 2 * k] | (rows[off + 30 - 2 * k] << xp.uint32(8))
            for k in range(16)]


def _k_recover_prelude(r, s, v, xp=jnp):
    """Checks + x-candidate + y^2 for the whole batch: mirrors the
    front of ``ec.ecrecover_point`` value-for-value.  ``v`` is the
    recovery id as a [B] u32 vector.  Returns (x, y_sq, ok)."""
    r_ok = (xp.uint32(1) - _k_is_zero(r, xp)) * _k_lt_const(r, _N_LIMBS_C, xp)
    s_ok = (xp.uint32(1) - _k_is_zero(s, xp)) * _k_lt_const(s, _N_LIMBS_C, xp)
    v_ok = (v < 4).astype(xp.uint32)
    hi = (v >= 2).astype(xp.uint32)
    # x = r + (v >= 2 ? N : 0), 17-limb carry chain
    mask = xp.uint32(MASK)
    x = []
    c = xp.zeros_like(r[0])
    for k in range(16):
        t = r[k] + hi * xp.uint32(_N_LIMBS_C[k]) + c
        x.append(t & mask)
        c = t >> 16
    x_ok = (c == 0).astype(xp.uint32) * _k_lt_const(x, _P_LIMBS, xp)
    y_sq = _k_mul(_k_sqr(x, xp), x, xp)
    seven = [xp.uint32(7) if k == 0 else xp.uint32(0) for k in range(16)]
    y_sq = _k_carry_tail([a + b for a, b in zip(y_sq, seven)], xp)
    return x, y_sq, r_ok * s_ok * v_ok * x_ok


def _recover_prelude_kernel(sig_ref, hash_ref, x_ref, ysq_ref, ok_ref,
                            r_ref, s_ref, z_ref, v_ref):
    """Wire bytes in, scalar-stage outputs out: unpacks r/s/v/z from
    the 65-byte signature + 32-byte hash rows IN-KERNEL (the byte
    shuffles ran as ~14 separate XLA dispatches), then the checks and
    y^2 candidate."""
    srows = [sig_ref[k, :] for k in range(65)]
    r = _k_unpack_be(srows, 0)
    s = _k_unpack_be(srows, 32)
    v = srows[64]
    z = _k_unpack_be([hash_ref[k, :] for k in range(32)], 0)
    x, y_sq, ok = _k_recover_prelude(r, s, v)
    _write16(x_ref, x)
    _write16(ysq_ref, y_sq)
    ok_ref[0, :] = ok
    _write16(r_ref, r)
    _write16(s_ref, s)
    _write16(z_ref, z)
    v_ref[0, :] = v


def recover_prelude_pallas(sigs, hashes, *, interpret=None):
    """``sigs [B, 65]`` u8 wire signatures, ``hashes [B, 32]`` u8 ->
    ``(x, y_sq, ok, r, s, z, v)`` — the unpacked limb fields ride out
    of the same launch that checks them."""
    if interpret is None:
        interpret = _default_interpret()
    B = sigs.shape[0]
    pad = (-B) % LANE_BLOCK
    st = jnp.pad(sigs.astype(jnp.uint32), ((0, pad), (0, 0))).T
    ht = jnp.pad(hashes.astype(jnp.uint32), ((0, pad), (0, 0))).T
    wide = st.shape[1]
    lim = jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32)
    row = jax.ShapeDtypeStruct((1, wide), jnp.uint32)
    lspec = pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i))
    rspec = pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i))
    x, ysq, ok, r, s, z, v = pl.pallas_call(
        _recover_prelude_kernel,
        out_shape=(lim, lim, row, lim, lim, lim, row),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((65, LANE_BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((32, LANE_BLOCK), lambda i: (0, i))],
        out_specs=(lspec, lspec, rspec, lspec, lspec, lspec, rspec),
        interpret=interpret,
    )(st, ht)
    return (x.T[:B], ysq.T[:B], ok[0, :B],
            r.T[:B], s.T[:B], z.T[:B], v[0, :B])


def _k_y_fix(root, y_sq, v, xp=jnp):
    """After the sqrt pow: canonicalize the root, verify it, fix parity
    to v&1.  Mirrors FP.sqrt's check + ecrecover_point's parity select.
    Returns (y, y_ok)."""
    rc = _k_cond_sub_p(_k_sqr(root, xp), xp)
    ac = _k_cond_sub_p(y_sq, xp)
    y_ok = xp.ones_like(root[0])
    for g, w in zip(rc, ac):
        y_ok = y_ok * (g == w).astype(xp.uint32)
    y0 = _k_cond_sub_p(root, xp)
    want_odd = v & xp.uint32(1)
    flip = want_odd ^ (y0[0] & xp.uint32(1))
    y = _k_select(flip, _k_neg(y0, xp), y0, xp)
    return y, y_ok


def _y_fix_kernel(root_ref, ysq_ref, v_ref, y_ref, ok_ref):
    y, ok = _k_y_fix(_read16(root_ref), _read16(ysq_ref), v_ref[0, :])
    _write16(y_ref, y)
    ok_ref[0, :] = ok


def y_fix_pallas(root, y_sq, v, *, interpret=None):
    """``(root, y_sq) [B, 16]`` relaxed, ``v [B]`` -> ``(y [B, 16],
    y_ok [B])``."""
    if interpret is None:
        interpret = _default_interpret()
    B = root.shape[0]
    pad = (-B) % LANE_BLOCK
    rt = jnp.pad(root, ((0, pad), (0, 0))).T
    at = jnp.pad(y_sq, ((0, pad), (0, 0))).T
    vt = jnp.pad(v.astype(jnp.uint32), (0, pad)).reshape(1, -1)
    wide = rt.shape[1]
    y, ok = pl.pallas_call(
        _y_fix_kernel,
        out_shape=(jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32),
                   jax.ShapeDtypeStruct((1, wide), jnp.uint32)),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i)),
                   pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i))),
        interpret=interpret,
    )(rt, at, vt)
    return y.T[:B], ok[0, :B]


def _k_u1u2(z, s, r_inv, xp=jnp):
    """u1 = -(z mod N) * r^-1, u2 = s * r^-1 (all canonical mod N);
    mirrors the u1/u2 block of ``ec.ecrecover_point``."""
    z_mod = _k_fn_red_cols(list(z) + [xp.zeros_like(z[0])], xp)
    u1 = _k_fn_neg(_k_fn_mul(z_mod, r_inv, xp), xp)
    u2 = _k_fn_mul(s, r_inv, xp)
    return u1, u2


def _u1u2_kernel(z_ref, s_ref, rinv_ref, u1_ref, u2_ref):
    u1, u2 = _k_u1u2(_read16(z_ref), _read16(s_ref), _read16(rinv_ref))
    _write16(u1_ref, u1)
    _write16(u2_ref, u2)


def u1u2_pallas(z, s, r_inv, *, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    B = z.shape[0]
    pad = (-B) % LANE_BLOCK
    ats = [jnp.pad(a, ((0, pad), (0, 0))).T for a in (z, s, r_inv)]
    wide = ats[0].shape[1]
    u1, u2 = pl.pallas_call(
        _u1u2_kernel,
        out_shape=tuple(jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32)
                        for _ in range(2)),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i))] * 3,
        out_specs=tuple(pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i))
                        for _ in range(2)),
        interpret=interpret,
    )(*ats)
    return u1.T[:B], u2.T[:B]


def _k_limbs_to_words_be(a, xp=jnp):
    """16 LE 16-bit limbs (one 256-bit value) -> 8 LE u32 words of the
    value's BIG-endian byte string (keccak input order)."""
    out = []
    for w in range(8):
        # BE bytes 4w..4w+3 come from limbs 15-2w (hi) and 14-2w (lo)
        hi_l = a[15 - 2 * w]
        lo_l = a[14 - 2 * w]
        b0 = hi_l >> xp.uint32(8)
        b1 = hi_l & xp.uint32(0xFF)
        b2 = lo_l >> xp.uint32(8)
        b3 = lo_l & xp.uint32(0xFF)
        out.append(b0 | (b1 << xp.uint32(8)) | (b2 << xp.uint32(16))
                   | (b3 << xp.uint32(24)))
    return out


def _k_recover_finish(X, Y, Z, zi_raw, ok_in, xp=jnp):
    """Jacobian result + raw (relaxed) Z-inverse + accumulated validity
    -> affine (qx, qy), final ok, and the padded keccak block words of
    qx||qy.  Mirrors ``to_affine`` + the final selects of
    ``ecrecover_point`` + the keccak prep of ``pubkey_to_address``."""
    inf = _k_is_zero_mod(Z, xp)
    zi = _k_cond_sub_p(zi_raw, xp)   # inv_batched canonicalizes
    zi2 = _k_sqr(zi, xp)
    x = _k_cond_sub_p(_k_mul(X, zi2, xp), xp)
    y = _k_cond_sub_p(_k_mul(Y, _k_mul(zi, zi2, xp), xp), xp)
    zero = [xp.zeros_like(x[0])] * 16
    x = _k_select(inf, zero, x, xp)
    y = _k_select(inf, zero, y, xp)
    ok = ok_in * (xp.uint32(1) - inf)
    qx = _k_select(ok, x, zero, xp)
    qy = _k_select(ok, y, zero, xp)
    words = (_k_limbs_to_words_be(qx, xp) + _k_limbs_to_words_be(qy, xp))
    # keccak padding for a 64-byte message in a 136-byte rate block:
    # byte 64 = 0x01 (word 16 lsb), byte 135 = 0x80 (word 33 msb)
    z0 = xp.zeros_like(words[0])
    words.append(z0 + xp.uint32(1))
    words += [z0] * 16
    words.append(z0 + xp.uint32(0x80000000))
    return qx, qy, ok, words


def _recover_finish_kernel(x_ref, y_ref, z_ref, zi_ref, ok_ref,
                           qx_ref, qy_ref, oko_ref, w_ref):
    qx, qy, ok, words = _k_recover_finish(
        _read16(x_ref), _read16(y_ref), _read16(z_ref), _read16(zi_ref),
        ok_ref[0, :])
    _write16(qx_ref, qx)
    _write16(qy_ref, qy)
    oko_ref[0, :] = ok
    for k in range(34):
        w_ref[k, :] = words[k]


def recover_finish_pallas(X, Y, Z, zi_raw, ok_in, *, interpret=None):
    """``(X, Y, Z, zi_raw) [B, 16]``, ``ok_in [B]`` -> ``(qx, qy
    [B, 16] canonical/masked, ok [B], words [34, Bpad])``."""
    if interpret is None:
        interpret = _default_interpret()
    B = X.shape[0]
    pad = (-B) % LANE_BLOCK
    ats = [jnp.pad(a, ((0, pad), (0, 0))).T for a in (X, Y, Z, zi_raw)]
    okt = jnp.pad(ok_in.astype(jnp.uint32), (0, pad)).reshape(1, -1)
    wide = ats[0].shape[1]
    qx, qy, ok, words = pl.pallas_call(
        _recover_finish_kernel,
        out_shape=(jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32),
                   jax.ShapeDtypeStruct((NLIMBS, wide), jnp.uint32),
                   jax.ShapeDtypeStruct((1, wide), jnp.uint32),
                   jax.ShapeDtypeStruct((34, wide), jnp.uint32)),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i))] * 4
        + [pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i)),
                   pl.BlockSpec((NLIMBS, LANE_BLOCK), lambda i: (0, i)),
                   pl.BlockSpec((1, LANE_BLOCK), lambda i: (0, i)),
                   pl.BlockSpec((34, LANE_BLOCK), lambda i: (0, i))),
        interpret=interpret,
    )(*ats, okt)
    return qx.T[:B], qy.T[:B], ok[0, :B], words


def _keccak_round_kernel(w_ref, st_ref):
    """ONE keccak-f round per grid step (grid = (batch, 24)).

    The unrolled 24-round body is the largest Mosaic kernel in the
    pipeline (~3.6k vector ops):
    rolling rounds onto the grid gives Mosaic a 24x smaller body to
    compile while keeping ONE pallas_call.  The 25x2 u32 state lives in
    the output ref, revisited across round steps (rounds are the minor
    grid dim, so the block stays resident); the final digest rows are
    gathered by the wrapper.  Gated by EGES_TPU_KECCAK_GRID until the
    on-chip compile-time A/B picks a default."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        zero = jnp.zeros_like(w_ref[0, :])
        for l in range(25):
            st_ref[l, :] = w_ref[2 * l, :] if l < 17 else zero
            st_ref[25 + l, :] = w_ref[2 * l + 1, :] if l < 17 else zero

    lo = [st_ref[l, :] for l in range(25)]
    hi = [st_ref[25 + l, :] for l in range(25)]
    # theta
    clo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20]
           for x in range(5)]
    chi_ = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20]
            for x in range(5)]
    for x in range(5):
        rl, rh = _k_rot64(clo[(x + 1) % 5], chi_[(x + 1) % 5], 1, jnp)
        dlo, dhi = clo[(x + 4) % 5] ^ rl, chi_[(x + 4) % 5] ^ rh
        for y in range(5):
            lo[x + 5 * y] = lo[x + 5 * y] ^ dlo
            hi[x + 5 * y] = hi[x + 5 * y] ^ dhi
    # rho + pi
    blo, bhi = [None] * 25, [None] * 25
    for x in range(5):
        for y in range(5):
            dl = y + 5 * ((2 * x + 3 * y) % 5)
            blo[dl], bhi[dl] = _k_rot64(lo[x + 5 * y], hi[x + 5 * y],
                                        _KECCAK_ROT[x][y], jnp)
    # chi
    for y in range(5):
        row_l = [blo[x + 5 * y] for x in range(5)]
        row_h = [bhi[x + 5 * y] for x in range(5)]
        for x in range(5):
            lo[x + 5 * y] = row_l[x] ^ (~row_l[(x + 1) % 5]
                                        & row_l[(x + 2) % 5])
            hi[x + 5 * y] = row_h[x] ^ (~row_h[(x + 1) % 5]
                                        & row_h[(x + 2) % 5])
    # iota — the only per-round constant: a 24-way scalar select chain
    # beats plumbing an SMEM table through the call for 2 u32s
    rc_lo = jnp.uint32(0)
    rc_hi = jnp.uint32(0)
    for i, c in enumerate(_KECCAK_RC):
        rc_lo = jnp.where(r == i, jnp.uint32(c & 0xFFFFFFFF), rc_lo)
        rc_hi = jnp.where(r == i, jnp.uint32(c >> 32), rc_hi)
    lo[0] = lo[0] ^ rc_lo
    hi[0] = hi[0] ^ rc_hi
    for l in range(25):
        st_ref[l, :] = lo[l]
        st_ref[25 + l, :] = hi[l]


def keccak_grid_enabled() -> bool:
    return os.environ.get("EGES_TPU_KECCAK_GRID", "") == "1"


def keccak_rows_pallas(words: jnp.ndarray, *,
                       interpret: bool | None = None) -> jnp.ndarray:
    """``[34, wide]`` block words (already limb-major) -> ``[8, wide]``
    digest words; the transpose-free twin of keccak_block_pallas for
    the fused pipeline."""
    if interpret is None:
        interpret = _default_interpret()
    wide = words.shape[1]
    if keccak_grid_enabled():
        st = pl.pallas_call(
            _keccak_round_kernel,
            out_shape=jax.ShapeDtypeStruct((50, wide), jnp.uint32),
            grid=(wide // LANE_BLOCK, 24),
            in_specs=[pl.BlockSpec((34, LANE_BLOCK), lambda b, r: (0, b))],
            out_specs=pl.BlockSpec((50, LANE_BLOCK), lambda b, r: (0, b)),
            interpret=interpret,
        )(words)
        # digest order lo0 hi0 lo1 hi1 … (squeeze order of the flat twin)
        return st[jnp.array([0, 25, 1, 26, 2, 27, 3, 28], jnp.int32), :]
    return pl.pallas_call(
        _keccak_kernel,
        out_shape=jax.ShapeDtypeStruct((8, wide), jnp.uint32),
        grid=(wide // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((34, LANE_BLOCK), lambda b: (0, b))],
        out_specs=pl.BlockSpec((8, LANE_BLOCK), lambda b: (0, b)),
        interpret=interpret,
    )(words)
