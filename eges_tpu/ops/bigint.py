"""Fixed-width 256-bit modular arithmetic for TPU (JAX).

The reference does all of this inside C libsecp256k1 with 64-bit limbs and
carry chains (ref: crypto/secp256k1/libsecp256k1/src/field_5x52_impl.h role).
TPUs have no native 64-bit integer datapath, so the TPU-native design is
different: a 256-bit integer is a vector of **16 little-endian limbs of 16
bits each, stored as uint32**.  Every op below is shape-polymorphic over
leading batch dimensions (``[..., 16]``), so a batch of B field elements is a
``[B, 16]`` uint32 array — rows map onto VPU lanes, and the whole pipeline
stays in native int32 hardware ops (no XLA 64-bit emulation):

* 16b x 16b limb products are < 2^32: a single uint32 multiply never wraps.
* Column accumulation splits products into lo/hi 16-bit halves, so every
  partial sum stays far below 2^32 (max ~2^21 for a 16x16 schoolbook).
* Carry propagation is a short static chain of shifts/masks.

Reduction uses the pseudo-Mersenne shape of both secp256k1 moduli
(``m = 2^256 - delta``): fold ``hi * delta`` back into the low words a fixed
number of times, then conditionally subtract.  Inverse and sqrt go through
Fermat (``a^(m-2)``, ``a^((m+1)/4)``) with a rolled ``lax.fori_loop`` over the
constant exponent bits so the compiled graph stays small.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 16
NLIMBS = 16  # 256 bits
MASK = (1 << LIMB_BITS) - 1

# secp256k1 field prime and group order (ref: crypto/secp256k1 constants).
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141


# ---------------------------------------------------------------------------
# host-side conversions (trace-time constants and tests)
# ---------------------------------------------------------------------------

def int_to_limbs(x: int, n: int = NLIMBS) -> np.ndarray:
    """Python int -> n little-endian 16-bit limbs (numpy uint32)."""
    if x < 0 or x >= 1 << (LIMB_BITS * n):
        raise ValueError("out of range")
    return np.array([(x >> (LIMB_BITS * i)) & MASK for i in range(n)], dtype=np.uint32)


def limbs_to_int(a) -> int:
    """Limb array (last axis) -> Python int.  Host/test use only."""
    a = np.asarray(a)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(a.reshape(-1)))


def bytes_be_to_limbs(b: jnp.ndarray) -> jnp.ndarray:
    """``[..., 32]`` big-endian bytes (uint8) -> ``[..., 16]`` limbs (uint32).

    In-graph unpacking for wire-format inputs (r/s/hash fields of the 65-byte
    signatures the reference passes to RecoverPubkey, secp256.go:105).
    """
    le = b[..., ::-1].astype(jnp.uint32)  # little-endian bytes
    pairs = le.reshape(*le.shape[:-1], NLIMBS, 2)
    return pairs[..., 0] | (pairs[..., 1] << 8)


def limbs_to_bytes_be(a: jnp.ndarray) -> jnp.ndarray:
    """``[..., 16]`` limbs -> ``[..., 32]`` big-endian bytes (uint8)."""
    lo = (a & 0xFF).astype(jnp.uint8)
    hi = ((a >> 8) & 0xFF).astype(jnp.uint8)
    le = jnp.stack([lo, hi], axis=-1).reshape(*a.shape[:-1], 2 * NLIMBS)
    return le[..., ::-1]


# ---------------------------------------------------------------------------
# carry chains and wide helpers
# ---------------------------------------------------------------------------

def _carry(cols: jnp.ndarray, n_out: int) -> jnp.ndarray:
    """Propagate carries over a column vector of small (<2^31) sums.

    Sequential but only ``cols.shape[-1]`` static steps of shift/mask.
    """
    out = []
    c = jnp.zeros(cols.shape[:-1], jnp.uint32)
    for k in range(cols.shape[-1]):
        t = cols[..., k] + c
        out.append(t & MASK)
        c = t >> LIMB_BITS
    while len(out) < n_out:
        out.append(c & MASK)
        c = c >> LIMB_BITS
    return jnp.stack(out[:n_out], axis=-1)


def big_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full product of two limb vectors: ``[..., na] x [..., nb] -> [..., na+nb]``.

    Diagonal-gather column sums (no scatter ops — ``.at[].add`` lowered
    to thousands of scatters across the recover graph and dominated its
    compile time) followed by one carry chain; all accumulators stay far
    below 2^32 (col sums < 2^21 for 16x16).
    """
    na, nb = a.shape[-1], b.shape[-1]
    return _carry(big_mul_cols(a, b), na + nb)


def big_add(a: jnp.ndarray, b: jnp.ndarray, n_out: int | None = None) -> jnp.ndarray:
    """Uncarried limb add then carry-fix; output width ``n_out``."""
    na, nb = a.shape[-1], b.shape[-1]
    w = max(na, nb)
    if n_out is None:
        n_out = w + 1
    pa = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w - na)])
    pb = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, w - nb)])
    return _carry(pa + pb, n_out)


def big_sub(a: jnp.ndarray, b: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``a - b`` with borrow chain (same width).  Returns (diff, borrow_flag).

    borrow_flag is 1 where ``a < b`` (diff then holds ``a - b + 2^(16n)``).
    """
    n = a.shape[-1]
    assert b.shape[-1] == n
    out = []
    borrow = jnp.zeros(a.shape[:-1], jnp.uint32)
    for k in range(n):
        # Work in uint32: add 2^16 headroom so the subtraction never wraps.
        t = a[..., k] + jnp.uint32(1 << LIMB_BITS) - b[..., k] - borrow
        out.append(t & MASK)
        borrow = jnp.uint32(1) - (t >> LIMB_BITS)
    return jnp.stack(out, axis=-1), borrow


def big_lt(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-row ``a < b`` as a uint32 0/1 flag."""
    _, borrow = big_sub(a, b)
    return borrow


def select(flag: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Limb-select: ``flag ? a : b`` with flag broadcast over the limb axis."""
    return jnp.where(flag[..., None].astype(bool), a, b)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """Per-row all-limbs-zero flag (uint32 0/1)."""
    return (jnp.max(a, axis=-1) == 0).astype(jnp.uint32)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-row limbwise equality flag (uint32 0/1)."""
    return jnp.all(a == b, axis=-1).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# modular arithmetic for a fixed pseudo-Mersenne modulus
# ---------------------------------------------------------------------------

class Mod:
    """Arithmetic mod a constant ``m = 2^256 - delta`` (secp256k1 P or N).

    All methods take/return ``[..., 16]`` uint32 limb arrays with values in
    ``[0, m)`` and are safe under jit/vmap.  Exponents for :meth:`pow_const`
    are Python-int constants, rolled into a ``fori_loop`` over their bits.
    """

    def __init__(self, m: int, n_folds: int):
        self.m = m
        delta = (1 << 256) - m
        self.delta_limbs_np = int_to_limbs(delta, (delta.bit_length() + 15) // 16)
        self.m_limbs_np = int_to_limbs(m)
        self.n_folds = n_folds

    @property
    def m_limbs(self) -> jnp.ndarray:
        return jnp.asarray(self.m_limbs_np)

    def _cond_sub_m(self, a: jnp.ndarray) -> jnp.ndarray:
        """One conditional subtract of m from a 16-limb value in [0, 2m)."""
        diff, borrow = big_sub(a, jnp.broadcast_to(self.m_limbs, a.shape))
        return select(borrow, a, diff)

    def red(self, wide: jnp.ndarray) -> jnp.ndarray:
        """Reduce a wide (>16 limb) value mod m via delta-folding.

        ``n_folds`` folds shrink a 512-bit value to ``< 2^256 + small``; one
        extra fold then guarantees the limbs above 256 bits are exactly zero
        (if the top limb was 1, the new value is ``old - m < m``), so the
        truncation below is lossless and two conditional subtracts finish.
        """
        delta = jnp.asarray(self.delta_limbs_np)
        for _ in range(self.n_folds + 1):
            if wide.shape[-1] <= NLIMBS:
                break
            lo = wide[..., :NLIMBS]
            hi = wide[..., NLIMBS:]
            prod = big_mul(hi, jnp.broadcast_to(delta, (*hi.shape[:-1], delta.shape[-1])))
            wide = big_add(lo, prod)
        a = wide[..., :NLIMBS]
        a = self._cond_sub_m(a)
        a = self._cond_sub_m(a)
        return a

    def add(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        s = big_add(a, b, NLIMBS + 1)
        return self.red(s)

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        # a - b mod m with a,b in [0, m): add m then subtract, always >= 0.
        am = big_add(a, jnp.broadcast_to(self.m_limbs, a.shape), NLIMBS + 1)
        bp = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, 1)])
        diff, _ = big_sub(am, bp)
        return self.red(diff)

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        z = jnp.zeros_like(a)
        return select(is_zero(a), z, self.sub(z, a))

    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self.red(big_mul(a, b))

    def sqr(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mul(a, a)

    def mul_small(self, a: jnp.ndarray, k: int) -> jnp.ndarray:
        """Multiply by a small Python-int constant (k < 2^16)."""
        kl = jnp.full((*a.shape[:-1], 1), k, jnp.uint32)
        return self.red(big_mul(a, kl))

    def pow_const(self, a: jnp.ndarray, e: int) -> jnp.ndarray:
        """``a ** e mod m`` for a constant exponent, via a rolled bit loop."""
        nbits = e.bit_length()
        bits = jnp.asarray([(e >> i) & 1 for i in range(nbits)], dtype=jnp.uint32)
        # Derive the constant from ``a`` (a*0 + 1) so its varying-axes type
        # matches ``a`` under shard_map: a fori_loop carry must keep a
        # consistent type across iterations (mixing an unvarying constant
        # with a device-varying base trips the vma check).
        one = a * 0 + jnp.asarray(int_to_limbs(1))

        def body(i, state):
            result, base = state
            bit = bits[i]
            result = select(jnp.broadcast_to(bit, result.shape[:-1]),
                            self.mul(result, base), result)
            base = self.sqr(base)
            return result, base

        result, _ = jax.lax.fori_loop(0, nbits, body, (one, a))
        return result

    def inv(self, a: jnp.ndarray) -> jnp.ndarray:
        """Fermat inverse ``a^(m-2)``; returns 0 for input 0."""
        return self.pow_const(a, self.m - 2)

    def batch_inv(self, a: jnp.ndarray) -> jnp.ndarray:
        """Montgomery batch inversion over the leading batch axis.

        A Fermat inverse costs ~512 field muls *per row*; the batch trick
        replaces that with a handful of full-width muls plus ONE Fermat
        inverse of the whole batch's product.  Implemented as rolled
        Hillis-Steele prefix/suffix product scans (``fori_loop`` whose
        body is a single batched mul — the earlier Python-unrolled
        product tree traced ~80k HLO ops and dominated compile time):

            P[i] = x[0] * ... * x[i]        (log2 B rolled steps)
            S[i] = x[i] * ... * x[B-1]      (log2 B rolled steps)
            inv[i] = P[i-1] * S[i+1] * (P[B-1])^-1

        Zero rows pass through as 0 (same contract as :meth:`inv`).
        ``a`` must be ``[B, 16]``; any B >= 1.
        """
        B = a.shape[0]
        if B == 1:
            return self.inv(a)
        one = jnp.broadcast_to(jnp.asarray(int_to_limbs(1)), a.shape)
        zero_mask = self.is_zero_mod(a)
        x = select(zero_mask, one, a)  # make every row invertible
        idx = jnp.arange(B, dtype=jnp.uint32)
        nlev = (B - 1).bit_length()

        def scan(v):
            def step(k, p):
                sh = (jnp.uint32(1) << k).astype(jnp.uint32)
                rolled = jnp.roll(p, sh.astype(jnp.int32), axis=0)
                contrib = select(idx >= sh, rolled, one)
                return self.mul(p, contrib)

            return jax.lax.fori_loop(0, nlev, step, v)

        prefix = scan(x)
        suffix = scan(x[::-1])[::-1]
        total_inv = self.inv(prefix[-1:])  # [1, 16]
        p_prev = select(idx >= 1, jnp.roll(prefix, 1, axis=0), one)
        s_next = select(idx < B - 1, jnp.roll(suffix, -1, axis=0), one)
        inv = self.mul(self.mul(p_prev, s_next),
                       jnp.broadcast_to(total_inv, a.shape))
        inv = self.canon(inv)
        return select(zero_mask, jnp.zeros_like(a), inv)

    def inv_batched(self, a: jnp.ndarray) -> jnp.ndarray:
        """Shape-polymorphic front door for :meth:`batch_inv`: flattens
        leading dims; falls back to Fermat for unbatched inputs.

        Under the fused-kernel variant (EGES_TPU_PALLAS=ladder, TPU
        backend) this routes to the streamed pow kernel instead: a
        direct per-row Fermat inverse costs more field muls than the
        Montgomery scan trick, but runs as ONE kernel launch where the
        scan + rolled pow pay thousands of tiny dispatches — and launch
        overhead, not arithmetic, bounds this backend (BENCH r4)."""
        if a.ndim < 2:
            return self.inv(a)
        flat = a.reshape(-1, NLIMBS)
        from eges_tpu.ops.pallas_kernels import (
            ladder_kernels_enabled, pow_mod_pallas,
        )
        if ladder_kernels_enabled() and self.m in (P, N):
            out = pow_mod_pallas(flat, self.m - 2,
                                 "p" if self.m == P else "n")
            if self.m == P:
                # batch_inv canonicalizes; match it bit-for-bit so the
                # fused variant stays differential-testable against the
                # graph path (the mod-N kernel is canonical already)
                out = self.canon(out)
            return out.reshape(a.shape)
        return self.batch_inv(flat).reshape(a.shape)

    def const(self, x: int, like: jnp.ndarray) -> jnp.ndarray:
        """Broadcast a Python-int constant to the batch shape of ``like``."""
        return jnp.broadcast_to(jnp.asarray(int_to_limbs(x % self.m)), like.shape)

    # canonical-representation hooks; FieldP overrides for its relaxed form
    def canon(self, a: jnp.ndarray) -> jnp.ndarray:
        return a

    def is_zero_mod(self, a: jnp.ndarray) -> jnp.ndarray:
        return is_zero(a)

    def eq_mod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return eq(a, b)


# ---------------------------------------------------------------------------
# fast path for F_P: diagonal-gather column products + fold-in-column-space
# reduction + relaxed representation
# ---------------------------------------------------------------------------
#
# The generic Mod path above scatters 32 partial rows into a column vector
# and walks three carry/borrow chains per multiply (~100 sequential steps,
# ~800 HLO ops).  The F_P fast path below does the same work as:
#   * ONE constant-index gather that lines the 16x16 partial-product matrix
#     up along its anti-diagonals plus a single sum-reduce ("column sums"),
#   * delta-folding performed directly on the (uncarried) columns —
#     977*hi and hi<<2 vector adds, exploiting delta_P = 2^32 + 977 having
#     a single tiny limb,
#   * exactly two 16-step carry chains and one 5-step mini-chain.
# Outputs are RELAXED: in [0, 2^256), possibly >= P.  All F_P ops accept
# relaxed inputs; canonicalize (one conditional subtract) only at compare/
# output sites via canon()/is_zero_mod()/eq_mod().  This matches how
# libsecp26k1's field_5x52 representation defers normalization — re-derived
# here for 16-bit lanes and XLA (no borrowed code; ref role:
# crypto/secp256k1/libsecp256k1/src/field_5x52_impl.h).


@functools.lru_cache(maxsize=None)
def _diag_idx(na: int, nb: int):
    """Constant gather indices/masks aligning M[i, j] along k = i + j."""
    k = np.arange(na + nb - 1)[None, :]
    i = np.arange(na)[:, None]
    j = k - i
    mask = ((j >= 0) & (j < nb)).astype(np.uint32)
    idx = np.clip(j, 0, nb - 1).astype(np.int32)
    return idx, mask  # numpy constants (jnp values must not be cached
    #                   across traces — they would leak tracers)


def big_mul_cols(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Uncarried column sums of ``a * b``: ``[..., na+nb]`` uint32.

    Column k holds ``sum_{i+j=k} lo(a_i b_j) + sum_{i+j=k-1} hi(a_i b_j)``
    < 2^21 for na = nb = 16.
    """
    na, nb = a.shape[-1], b.shape[-1]
    prod = a[..., :, None] * b[..., None, :]  # [., na, nb]
    lo = prod & MASK
    hi = prod >> LIMB_BITS
    idx_np, mask_np = _diag_idx(na, nb)
    idx, mask = jnp.asarray(idx_np), jnp.asarray(mask_np)
    K = na + nb - 1
    bidx = jnp.broadcast_to(idx, (*prod.shape[:-2], na, K))
    lo_d = (jnp.take_along_axis(lo, bidx, axis=-1) * mask).sum(axis=-2)
    hi_d = (jnp.take_along_axis(hi, bidx, axis=-1) * mask).sum(axis=-2)
    zero = jnp.zeros((*lo_d.shape[:-1], 1), jnp.uint32)
    return (jnp.concatenate([lo_d, zero], axis=-1)
            + jnp.concatenate([zero, hi_d], axis=-1))


class FieldP(Mod):
    """The base field F_P: fast relaxed arithmetic + sqrt (P ≡ 3 mod 4)."""

    def __init__(self):
        super().__init__(P, n_folds=3)
        # constant for branchless subtraction: a - b ≡
        #   a + (0xFFFF - b) + (2^256 - 2*delta + 1)  (mod P), see sub()
        self._subc_np = int_to_limbs((1 << 256) - 2 * ((1 << 256) - P) + 1)
        # EGES_TPU_PALLAS=1 routes equal-shape batched multiplies through
        # the hand-tiled Pallas kernel (ops/pallas_kernels.py) — a
        # measurement hook for TPU A/B runs, not a default (per-mul
        # pallas_call boundaries forgo XLA fusion between field ops)
        import os as _os
        self._use_pallas = _os.environ.get("EGES_TPU_PALLAS", "") == "1"

    # -- the shared reduction tail ---------------------------------------

    def _reduce_cols(self, cols: jnp.ndarray) -> jnp.ndarray:
        """Columns (each < 2^31, width <= 32) -> relaxed 16-limb value.

        Bound contract: the two fold iterations below stay under 2^32
        when input columns are < 2^21 (multiplication) or < 2^19
        (add/sub/mul_small); see the inline bounds.
        """
        # fold columns >= 16 into the low 16 via delta = 2^32 + 977
        # (pad-and-add, NOT .at[].add — scatters are poison for both
        # XLA compile time and TPU lowering)
        while cols.shape[-1] > 16:
            lo = cols[..., :16]
            hi = cols[..., 16:]
            h = hi.shape[-1]
            w = max(16, h + 2)
            pad = [(0, 0)] * (cols.ndim - 1)
            lo_w = jnp.concatenate(
                [lo, jnp.zeros((*lo.shape[:-1], w - 16), jnp.uint32)],
                axis=-1) if w > 16 else lo
            # col j   += 977 * hi_j   (j < h;    977*2^21 < 2^31)
            t977 = jnp.pad(hi * jnp.uint32(977), pad + [(0, w - h)])
            # col j+2 += hi_j         (2^21)
            tsh = jnp.pad(hi, pad + [(2, w - h - 2)])
            cols = lo_w + t977 + tsh
        # first full carry: 16 columns < 2^32 -> limbs + c_top < 2^16+eps
        out = []
        c = jnp.zeros(cols.shape[:-1], jnp.uint32)
        for k in range(16):
            t = cols[..., k] + c
            out.append(t & MASK)
            c = t >> LIMB_BITS
        # fold c_top * 2^256 ≡ c_top * delta
        out[0] = out[0] + c * jnp.uint32(977)  # < 2^16 + 2^26
        out[2] = out[2] + c
        # second full carry
        c = jnp.zeros_like(c)
        for k in range(16):
            t = out[k] + c
            out[k] = t & MASK
            c = t >> LIMB_BITS
        # possible final wrap: value was < 2^256 + 2^49, so if c == 1 the
        # remaining limbs above index 3 are zero and a 5-step chain closes
        out[0] = out[0] + c * jnp.uint32(977)
        out[2] = out[2] + c
        cc = jnp.zeros_like(c)
        for k in range(5):
            t = out[k] + cc
            out[k] = t & MASK
            cc = t >> LIMB_BITS
        return jnp.stack(out, axis=-1)

    # -- relaxed ops ------------------------------------------------------

    @staticmethod
    def _glue(*arrs) -> bool:
        """Route this call site through its one-launch Pallas glue
        kernel?  True on the fused-kernel variant (TPU backends) for
        batched same-shape 16-limb operands — the round-4 census showed
        the XLA forms of these ops execute as ~3.8k separate dispatches
        per recover on hardware."""
        from eges_tpu.ops.pallas_kernels import ladder_kernels_enabled
        if not ladder_kernels_enabled():
            return False
        first = arrs[0]
        return all(getattr(a, "ndim", 0) >= 2 and a.shape == first.shape
                   and a.shape[-1] == NLIMBS for a in arrs)

    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        if (self._use_pallas or self._glue(a, b)) \
                and a.ndim >= 2 and a.shape == b.shape:
            from eges_tpu.ops.pallas_kernels import fp_mul_pallas
            flat = fp_mul_pallas(a.reshape(-1, NLIMBS),
                                 b.reshape(-1, NLIMBS))
            return flat.reshape(a.shape)
        return self._reduce_cols(big_mul_cols(a, b))

    def sqr(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mul(a, a)

    def add(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        if self._glue(a, b):
            from eges_tpu.ops.pallas_kernels import fp_add_pallas
            return fp_add_pallas(a.reshape(-1, NLIMBS),
                                 b.reshape(-1, NLIMBS)).reshape(a.shape)
        return self._reduce_cols(a + b)  # cols < 2^17

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Branchless: a + (0xFFFF - b) + C where C = 2^256 - 2*delta + 1,
        so the column value is a - b + 2P >= 0 — no borrow chain."""
        if self._glue(a, b):
            from eges_tpu.ops.pallas_kernels import fp_sub_pallas
            return fp_sub_pallas(a.reshape(-1, NLIMBS),
                                 b.reshape(-1, NLIMBS)).reshape(a.shape)
        comp = jnp.uint32(MASK) - b
        subc = jnp.broadcast_to(jnp.asarray(self._subc_np), a.shape)
        return self._reduce_cols(a + comp + subc)  # cols < 3*2^16

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        if self._glue(a):
            from eges_tpu.ops.pallas_kernels import fp_neg_pallas
            return fp_neg_pallas(a.reshape(-1, NLIMBS)).reshape(a.shape)
        return self.sub(jnp.zeros_like(a), a)

    def mul_small(self, a: jnp.ndarray, k: int) -> jnp.ndarray:
        assert k < 16
        if self._glue(a):
            from eges_tpu.ops.pallas_kernels import fp_mul_small_pallas
            return fp_mul_small_pallas(
                a.reshape(-1, NLIMBS), k).reshape(a.shape)
        return self._reduce_cols(a * jnp.uint32(k))  # cols < 2^20

    # -- canonicalization ------------------------------------------------

    def canon(self, a: jnp.ndarray) -> jnp.ndarray:
        """Relaxed [0, 2^256) -> canonical [0, P): one conditional
        subtract (2^256 - P < P, so one is always enough)."""
        if self._glue(a):
            from eges_tpu.ops.pallas_kernels import fp_canon_pallas
            return fp_canon_pallas(a.reshape(-1, NLIMBS)).reshape(a.shape)
        return self._cond_sub_m(a)

    def is_zero_mod(self, a: jnp.ndarray) -> jnp.ndarray:
        """a ≡ 0 (mod P) for relaxed a: value is exactly 0 or P."""
        return (is_zero(a) | eq(a, jnp.broadcast_to(self.m_limbs, a.shape)))

    def eq_mod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return eq(self.canon(a), self.canon(b))

    def sqrt(self, a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Square root via ``a^((P+1)/4)``.  Returns (root, exists_flag).

        Fused-kernel variant: the rolled 254-bit pow ladder becomes one
        streamed kernel launch (callers canonicalize the root before
        consuming its bits, so the two paths' relaxed encodings may
        differ while the residue — and every downstream bit — agrees)."""
        from eges_tpu.ops.pallas_kernels import (
            ladder_kernels_enabled, pow_mod_pallas,
        )
        if ladder_kernels_enabled() and a.ndim == 2:
            r = pow_mod_pallas(a, (P + 1) // 4, "p")
        else:
            r = self.pow_const(a, (P + 1) // 4)
        ok = self.eq_mod(self.sqr(r), a)
        return r, ok


class OrderN(Mod):
    """The scalar field mod the group order N, with a column-space fast
    multiply: the generic ``big_mul + red`` path walks ~6 carry chains
    per multiply; here each delta-fold carries the high part once and
    accumulates the fold product as uncarried columns, so a full modular
    multiply costs 3 short chains total (delta_N is 129 bits = 9 limbs,
    so three folds shrink 512 -> <257 bits: 32 -> 26 -> 20 -> 16+eps)."""

    def __init__(self):
        super().__init__(N, n_folds=3)

    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        # EGES_TPU_PALLAS=ladder on hardware: the mod-N multiply rides
        # its Mosaic kernel alongside the fused ladder step (only ~8
        # calls per recover — the win is uniformity, not throughput)
        from eges_tpu.ops.pallas_kernels import ladder_kernels_enabled
        if ladder_kernels_enabled() and a.ndim >= 2 and a.shape == b.shape:
            from eges_tpu.ops.pallas_kernels import fn_mul_pallas
            return fn_mul_pallas(a.reshape(-1, NLIMBS),
                                 b.reshape(-1, NLIMBS)).reshape(a.shape)
        return self._red_cols(big_mul_cols(a, b))

    def sqr(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mul(a, a)

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        if FieldP._glue(a, b):
            from eges_tpu.ops.pallas_kernels import fn_sub_pallas
            return fn_sub_pallas(a.reshape(-1, NLIMBS),
                                 b.reshape(-1, NLIMBS)).reshape(a.shape)
        return super().sub(a, b)

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        if FieldP._glue(a):
            from eges_tpu.ops.pallas_kernels import fn_neg_pallas
            return fn_neg_pallas(a.reshape(-1, NLIMBS)).reshape(a.shape)
        return super().neg(a)

    def red(self, wide: jnp.ndarray) -> jnp.ndarray:
        # the 17-limb reduction (z mod N, px mod N) as one glue launch
        from eges_tpu.ops.pallas_kernels import ladder_kernels_enabled
        if (ladder_kernels_enabled() and getattr(wide, "ndim", 0) >= 2
                and wide.shape[-1] == NLIMBS + 1):
            from eges_tpu.ops.pallas_kernels import fn_red17_pallas
            return fn_red17_pallas(
                wide.reshape(-1, NLIMBS + 1)).reshape(*wide.shape[:-1],
                                                      NLIMBS)
        # carried limbs are valid (small) columns — same fast reducer
        return self._red_cols(wide)

    def _red_cols(self, cols: jnp.ndarray) -> jnp.ndarray:
        """Uncarried columns (< 2^22 each) -> canonical [0, N)."""
        delta = jnp.asarray(self.delta_limbs_np)  # 9 limbs
        nd = delta.shape[-1]
        pad = [(0, 0)] * (cols.ndim - 1)
        while cols.shape[-1] > 16:
            lo = cols[..., :16]
            # carry the high columns into clean limbs before multiplying
            # by delta (uncarried cols x delta limbs would overflow u32)
            hi = _carry(cols[..., 16:], cols.shape[-1] - 16 + 1)
            prod = big_mul_cols(hi, jnp.broadcast_to(
                delta, (*hi.shape[:-1], nd)))  # uncarried, < 2^21
            w = max(16, prod.shape[-1])
            lo_w = jnp.pad(lo, pad + [(0, w - 16)])
            pr_w = jnp.pad(prod, pad + [(0, w - prod.shape[-1])])
            cols = lo_w + pr_w
        a = _carry(cols, 17)
        # fold the top limb twice: the first fold can still push the
        # value past 2^256 (top < 2^7 here), the second cannot (top <= 1)
        for _ in range(2):
            top = a[..., 16:17]
            fold = jnp.pad(top * delta, pad + [(0, 16 - nd)])
            a = _carry(a[..., :16] + fold, 17)
        a = a[..., :16]
        a = self._cond_sub_m(a)
        return self._cond_sub_m(a)


FP = FieldP()
FN = OrderN()
