"""Differential test: the Pallas F_P-multiply kernel must agree
bit-for-bit with the XLA-graph path (interpret mode on CPU; the same
kernel lowers via Mosaic on a real TPU)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eges_tpu.ops.bigint import FP, P, int_to_limbs, limbs_to_int
from eges_tpu.ops.pallas_kernels import fp_mul_pallas

rng = random.Random(99)


def _rand_batch(n):
    vals = [rng.randrange(P) for _ in range(n)]
    arr = np.stack([int_to_limbs(v) for v in vals])
    return vals, jnp.asarray(arr)


def test_fp_mul_kernel_matches_graph_path():
    n = 300  # not a LANE_BLOCK multiple: exercises padding
    va, a = _rand_batch(n)
    vb, b = _rand_batch(n)
    got = np.asarray(fp_mul_pallas(a, b, interpret=True))
    want = np.asarray(FP.mul(a, b))
    np.testing.assert_array_equal(got, want)
    # and both equal the mathematical product mod P
    for i in range(0, n, 37):
        assert limbs_to_int(got[i]) % P == (va[i] * vb[i]) % P


def test_fp_mul_kernel_extremes():
    vals = [0, 1, P - 1, P, (1 << 256) - 1 - 2 * ((1 << 256) - P)]
    arr = jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))
    got = np.asarray(fp_mul_pallas(arr, arr, interpret=True))
    want = np.asarray(FP.mul(arr, arr))
    np.testing.assert_array_equal(got, want)
    for v, row in zip(vals, got):
        assert limbs_to_int(row) % P == (v * v) % P


def _rand_point_batch(n):
    """Random affine points (as d*G host-side) lifted to Jacobian with a
    random Z scaling, so X/Y/Z exercise full-width limbs."""
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.ops.ec import GX_INT, GY_INT

    xs, ys, zs = [], [], []
    for _ in range(n):
        d = rng.randrange(1, host.N)
        x, y = host.point_mul(d, (GX_INT, GY_INT))
        z = rng.randrange(1, P)
        z2 = z * z % P
        xs.append(int_to_limbs(x * z2 % P))
        ys.append(int_to_limbs(y * z * z2 % P))
        zs.append(int_to_limbs(z))
    return (jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys)),
            jnp.asarray(np.stack(zs)))


def _affine_batch(n):
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.ops.ec import GX_INT, GY_INT

    xs, ys = [], []
    for _ in range(n):
        d = rng.randrange(1, host.N)
        x, y = host.point_mul(d, (GX_INT, GY_INT))
        xs.append(int_to_limbs(x))
        ys.append(int_to_limbs(y))
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))


def _t(arr):
    """[B, 16] array -> limb-major list of 16 numpy [B]-vectors."""
    a = np.asarray(arr)
    return [a[:, k].copy() for k in range(16)]


def _untq(limbs):
    return np.stack([np.asarray(v) for v in limbs], axis=-1)


def test_k_jac_double_matches_graph_path():
    """The in-kernel doubling math (numpy namespace) is bit-identical
    to ec.jac_double — including a chained 4x run (the double4 kernel
    body) and an infinity row."""
    from eges_tpu.ops.ec import jac_double
    from eges_tpu.ops.pallas_kernels import _k_jac_double

    n = 9
    pt = _rand_point_batch(n)
    pt = tuple(jnp.concatenate([t, jnp.zeros((1, 16), jnp.uint32)])
               for t in pt)
    K = [_t(t) for t in pt]
    want = pt
    for _ in range(4):
        want = jac_double(want)
        K = _k_jac_double(*K, xp=np)
        for g, w in zip(K, want):  # compare every step, not just the end
            np.testing.assert_array_equal(_untq(g), np.asarray(w))


def test_k_jac_add_mixed_matches_graph_path():
    """The in-kernel conditional-add math must equal the strauss_gR
    composition: per-row y-negation, branchless mixed add (incl.
    infinity/double/opposite cases), digit!=0 select."""
    from eges_tpu.ops.bigint import select
    from eges_tpu.ops.ec import jac_add_mixed
    from eges_tpu.ops.pallas_kernels import (
        _k_jac_add_mixed, _k_neg, _k_select,
    )

    n = 8
    pt = _rand_point_batch(n)
    px, py = _affine_batch(n)

    # craft exceptional rows: 0 = generic, 1 = same point (doubling),
    # 2 = opposite point (-> infinity), 3 = acc at infinity
    pt_l = [np.asarray(t).copy() for t in pt]
    px_l, py_l = np.asarray(px).copy(), np.asarray(py).copy()
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.ops.ec import GX_INT, GY_INT
    x1, y1 = host.point_mul(5, (GX_INT, GY_INT))
    for row, y_val in ((1, y1), (2, P - y1)):
        pt_l[0][row] = int_to_limbs(x1)
        pt_l[1][row] = int_to_limbs(y1)
        pt_l[2][row] = int_to_limbs(1)
        px_l[row] = int_to_limbs(x1)
        py_l[row] = int_to_limbs(y_val)
    pt_l[2][3] = 0  # infinity acc
    pt = tuple(jnp.asarray(t) for t in pt_l)
    px, py = jnp.asarray(px_l), jnp.asarray(py_l)

    neg = np.asarray([0, 0, 0, 0, 1, 1, 0, 1], np.uint32)
    nz = np.asarray([1, 1, 1, 1, 1, 0, 1, 1], np.uint32)

    # graph-path reference (the exact strauss_gR add-step composition)
    y_t = select(jnp.asarray(neg), FP.neg(py), py)
    added = jac_add_mixed(pt, px, jnp.asarray(y_t))
    want = tuple(select(jnp.asarray(nz), a, o)
                 for a, o in zip(added, pt))

    # in-kernel math, numpy namespace (the conditional-add step the
    # streamed ladder kernel runs per window operand)
    X, Y, Z = _t(pt[0]), _t(pt[1]), _t(pt[2])
    pxl, pyl = _t(px), _t(py)
    pyl = _k_select(neg, _k_neg(pyl, xp=np), pyl, xp=np)
    AX, AY, AZ = _k_jac_add_mixed(X, Y, Z, pxl, pyl, xp=np)
    got = (_k_select(nz, AX, X, xp=np), _k_select(nz, AY, Y, xp=np),
           _k_select(nz, AZ, Z, xp=np))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_untq(g), np.asarray(w))


def test_point_table_math_matches_graph_path():
    """The table kernel's numpy twin is bit-identical to the lax.scan
    of mixed adds in ec._build_point_table (entries 2..15)."""
    import jax.lax

    from eges_tpu.ops.ec import jac_add_mixed, _const
    from eges_tpu.ops.pallas_kernels import point_table_np

    n = 5
    px, py = _affine_batch(n)
    one = (px, py, _const(1, px))

    def step(cur, _):
        nxt = jac_add_mixed(cur, px, py)
        return nxt, nxt

    _, want = jax.lax.scan(step, one, None, length=14)
    got = point_table_np(np.asarray(px), np.asarray(py))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, np.asarray(w))


def test_pow_kernel_math_matches_graph():
    """The windowed-pow kernel math (numpy twin) computes the same
    residues as the rolled pow_const ladders: relaxed encodings may
    differ for F_P (different algorithm), canonical mod-N is bit-equal."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.pallas_kernels import pow_mod_np

    vals = [0, 1, 2, P - 1, P, rng.randrange(P), rng.randrange(P)]
    a = np.stack([int_to_limbs(v) for v in vals]).astype(np.uint32)

    for e in (P - 2, (P + 1) // 4):
        got = pow_mod_np(a, e, "p")
        for v, row in zip(vals, got):
            assert limbs_to_int(row) % P == pow(v % P, e, P)

    kvals = [0, 1, N - 1, rng.randrange(N), rng.randrange(N)]
    k = np.stack([int_to_limbs(v) for v in kvals]).astype(np.uint32)
    got = pow_mod_np(k, N - 2, "n")
    want = np.asarray(FN.pow_const(jnp.asarray(k), N - 2))
    np.testing.assert_array_equal(got, want)  # canonical: bit-equal
    for v, row in zip(kvals, got):
        assert limbs_to_int(row) == pow(v, N - 2, N)


def test_keccak_kernel_math_matches_golden():
    """The in-kernel keccak permutation (numpy twin) must reproduce the
    host golden keccak256 for single-block messages of both ecrecover-
    relevant lengths (64-byte pubkey, 32-byte scalar)."""
    from eges_tpu.crypto.keccak import keccak256
    from eges_tpu.ops.keccak_tpu import RATE
    from eges_tpu.ops.pallas_kernels import _k_keccak_words

    msgs = [bytes(range(64)), b"\x00" * 64, b"\xff" * 64,
            rng.randbytes(64), rng.randbytes(32), b""]
    B = len(msgs)
    words = np.zeros((B, 34), np.uint32)
    for i, m in enumerate(msgs):
        buf = bytearray(RATE)
        buf[: len(m)] = m
        buf[len(m)] ^= 0x01
        buf[RATE - 1] ^= 0x80
        words[i] = np.frombuffer(bytes(buf), "<u4")
    out = _k_keccak_words([words[:, k].copy() for k in range(34)], np)
    digests = np.stack(out, axis=-1).astype("<u4").view(np.uint8) \
        .reshape(B, 32)
    for i, m in enumerate(msgs):
        assert bytes(digests[i]) == keccak256(m), f"msg {i}"


def test_k_fn_mul_matches_graph_path():
    """The in-kernel mod-N multiply (numpy namespace) is bit-identical
    to OrderN.mul — canonical outputs, random + extreme operands."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.pallas_kernels import _k_fn_mul

    vals = [0, 1, N - 1, N - 2, (1 << 256) // 3]
    vals += [rng.randrange(N) for _ in range(11)]
    va = [v % N for v in vals]
    vb = list(reversed(va))
    a = jnp.asarray(np.stack([int_to_limbs(v) for v in va]))
    b = jnp.asarray(np.stack([int_to_limbs(v) for v in vb]))
    want = np.asarray(FN.mul(a, b))
    got = _untq(_k_fn_mul(_t(a), _t(b), xp=np))
    np.testing.assert_array_equal(got, want)
    for x, y, row in zip(va, vb, got):
        assert limbs_to_int(row) == (x * y) % N


@pytest.mark.slow
def test_fn_mul_kernel_interpret():
    """The mod-N kernel through pallas_call (interpret mode): covers
    the kernel plumbing at a size XLA CPU can still compile."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.pallas_kernels import fn_mul_pallas

    n = 5
    va = [rng.randrange(N) for _ in range(n)]
    vb = [rng.randrange(N) for _ in range(n)]
    a = jnp.asarray(np.stack([int_to_limbs(v) for v in va]))
    b = jnp.asarray(np.stack([int_to_limbs(v) for v in vb]))
    got = np.asarray(fn_mul_pallas(a, b, interpret=True))
    want = np.asarray(FN.mul(a, b))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# glue kernels (round 4): every remaining field op of the recover
# pipeline as one launch — numpy-twin math + interpret-mode plumbing
# ---------------------------------------------------------------------------


def test_glue_fp_kernel_math():
    """_k_add/_k_sub/_k_neg/_k_mul_small/_k_cond_sub_p (numpy namespace)
    are bit-identical to the FieldP graph ops on random + extreme rows."""
    from eges_tpu.ops.pallas_kernels import (
        _k_add, _k_sub, _k_neg, _k_mul_small, _k_cond_sub_p,
    )

    vals = [0, 1, P - 1, P, (1 << 256) - 1, rng.randrange(1 << 256)]
    vals += [rng.randrange(P) for _ in range(6)]
    vb = list(reversed(vals))
    a = jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))
    b = jnp.asarray(np.stack([int_to_limbs(v) for v in vb]))
    ta, tb = _t(a), _t(b)

    np.testing.assert_array_equal(_untq(_k_add(ta, tb, xp=np)),
                                  np.asarray(FP._reduce_cols(a + b)))
    comp = jnp.uint32(0xFFFF) - b
    subc = jnp.broadcast_to(jnp.asarray(FP._subc_np), a.shape)
    np.testing.assert_array_equal(
        _untq(_k_sub(ta, tb, xp=np)),
        np.asarray(FP._reduce_cols(a + comp + subc)))
    np.testing.assert_array_equal(
        _untq(_k_neg(ta, xp=np)),
        np.asarray(FP._reduce_cols(jnp.zeros_like(a)
                                   + (jnp.uint32(0xFFFF) - a) + subc)))
    for k in (2, 3, 8):
        np.testing.assert_array_equal(
            _untq(_k_mul_small(ta, k, xp=np)),
            np.asarray(FP._reduce_cols(a * jnp.uint32(k))))
    np.testing.assert_array_equal(_untq(_k_cond_sub_p(ta, xp=np)),
                                  np.asarray(FP._cond_sub_m(a)))


def test_glue_fn_kernel_math():
    """_k_fn_sub/_k_fn_neg/_k_fn_red_cols (numpy) match the canonical
    OrderN graph ops exactly."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.pallas_kernels import (
        _k_fn_neg, _k_fn_red_cols, _k_fn_sub,
    )

    vals = [0, 1, N - 1, N - 2, rng.randrange(N), rng.randrange(N)]
    vb = list(reversed(vals))
    a = jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))
    b = jnp.asarray(np.stack([int_to_limbs(v) for v in vb]))

    got = _untq(_k_fn_sub(_t(a), _t(b), xp=np))
    np.testing.assert_array_equal(got, np.asarray(FN.sub(a, b)))
    for x, y, row in zip(vals, vb, got):
        assert limbs_to_int(row) == (x - y) % N

    got = _untq(_k_fn_neg(_t(a), xp=np))
    np.testing.assert_array_equal(got, np.asarray(FN.neg(a)))

    # 17-limb reduction (the z-mod-N / px-mod-N path)
    wide_vals = [0, 1, N, N + 1, (1 << 256) - 1,
                 rng.randrange(1 << 256), rng.randrange(1 << 256)]
    w = jnp.asarray(np.stack([int_to_limbs(v, 17) for v in wide_vals]))
    cols = [np.asarray(w)[:, k].copy() for k in range(17)]
    got = _untq(_k_fn_red_cols(cols, xp=np))
    np.testing.assert_array_equal(got, np.asarray(FN._red_cols(w)))
    for v, row in zip(wide_vals, got):
        assert limbs_to_int(row) == v % N


def test_glue_mulhi8_math():
    """The GLV rounding kernel math: limbs 24..31 of k * g for the two
    lattice constants, vs the XLA big_mul path."""
    from eges_tpu.ops import bigint
    from eges_tpu.ops.ec import _G_G1, _G_G2
    from eges_tpu.ops.pallas_kernels import _k_carry, _k_mul_cols

    vals = [0, 1, bigint.N - 1, rng.randrange(bigint.N),
            rng.randrange(bigint.N)]
    k = jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))
    for g in (_G_G1, _G_G2):
        g_limbs = [int(v) for v in int_to_limbs(g)]
        cols = _k_mul_cols(_t(k), g_limbs, xp=np)
        got = _untq(_k_carry(cols, 32, xp=np)[24:32])
        gb = jnp.broadcast_to(jnp.asarray(int_to_limbs(g, 16)), k.shape)
        want = np.asarray(bigint.big_mul(k, gb)[..., 24:32])
        np.testing.assert_array_equal(got, want)
        for v, row in zip(vals, got):
            assert limbs_to_int(row) == ((v * g) >> 384) & ((1 << 128) - 1)


@pytest.mark.slow
def test_glue_kernels_interpret():
    """The glue kernels through pallas_call in interpret mode: covers
    the [rows, B] tiling plumbing (incl. the non-16-row operands)."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.ec import _G_G1
    from eges_tpu.ops import bigint
    from eges_tpu.ops.pallas_kernels import (
        fn_red17_pallas, fn_sub_pallas, fp_add_pallas, fp_canon_pallas,
        mulhi8_pallas,
    )

    n = 5
    va = [rng.randrange(P) for _ in range(n)]
    vb = [rng.randrange(P) for _ in range(n)]
    a = jnp.asarray(np.stack([int_to_limbs(v) for v in va]))
    b = jnp.asarray(np.stack([int_to_limbs(v) for v in vb]))
    np.testing.assert_array_equal(
        np.asarray(fp_add_pallas(a, b, interpret=True)),
        np.asarray(FP._reduce_cols(a + b)))
    np.testing.assert_array_equal(
        np.asarray(fp_canon_pallas(a, interpret=True)),
        np.asarray(FP._cond_sub_m(a)))

    ka = jnp.asarray(np.stack([int_to_limbs(v % N) for v in va]))
    kb = jnp.asarray(np.stack([int_to_limbs(v % N) for v in vb]))
    np.testing.assert_array_equal(
        np.asarray(fn_sub_pallas(ka, kb, interpret=True)),
        np.asarray(FN.sub(ka, kb)))

    w = jnp.asarray(np.stack([int_to_limbs(rng.randrange(1 << 256), 17)
                              for _ in range(n)]))
    np.testing.assert_array_equal(
        np.asarray(fn_red17_pallas(w, interpret=True)),
        np.asarray(FN._red_cols(w)))

    gb = jnp.broadcast_to(jnp.asarray(int_to_limbs(_G_G1, 16)), ka.shape)
    np.testing.assert_array_equal(
        np.asarray(mulhi8_pallas(ka, _G_G1, interpret=True)),
        np.asarray(bigint.big_mul(ka, gb)[..., 24:32]))


def test_strauss_tab_math_matches_graph_path():
    """The self-gathering ladder kernel (round-4 v2): in-kernel one-hot
    table lookups + sign folds must reproduce the plain XLA strauss_gR
    bit-for-bit, consuming exactly what pack_strauss_tab_inputs feeds
    the real kernel (digit order, sign rows, re-rowed R tables, lane
    padding)."""
    from eges_tpu.ops import ec
    from eges_tpu.ops.bigint import N
    from eges_tpu.ops.pallas_kernels import strauss_tab_np

    n = 4
    rx, ry = _affine_batch(n)
    u1_l = [0, 1, rng.randrange(N), rng.randrange(N)]  # incl. zero scalar
    u2_l = [rng.randrange(N), 0, 1, rng.randrange(N)]
    u1 = jnp.asarray(np.stack([int_to_limbs(v) for v in u1_l]))
    u2 = jnp.asarray(np.stack([int_to_limbs(v) for v in u2_l]))

    (digits, negs, _, _, r_tab) = ec._strauss_prelude(u1, u2, rx, ry)
    args = ec.pack_strauss_tab_inputs(digits, negs, r_tab)
    got = strauss_tab_np(*[np.asarray(a) for a in args])
    want = ec.strauss_gR(u1, u2, rx, ry)  # plain XLA path (CPU backend)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_untq(g)[:n], np.asarray(w))


def test_glv_digits_kernel_matches_graph_path():
    """The GLV-decompose kernel's math (numpy twin) must emit exactly
    the digit/sign arrays the XLA prelude builds (same lattice split,
    sign test, digit order) for random and edge scalars."""
    from eges_tpu.ops import ec
    from eges_tpu.ops.bigint import N
    from eges_tpu.ops.pallas_kernels import glv_digits_np

    n = 6
    vals1 = [0, 1, N - 1, rng.randrange(N), rng.randrange(N),
             rng.randrange(N)]
    vals2 = [N - 2, 0, 1, rng.randrange(N), rng.randrange(N), 2]
    u1 = jnp.asarray(np.stack([int_to_limbs(v) for v in vals1]))
    u2 = jnp.asarray(np.stack([int_to_limbs(v) for v in vals2]))

    k1s, n1s, k2s, n2s = ec._glv_decompose(jnp.stack([u1, u2]))
    digits = (ec._digits33(k1s[0]), ec._digits33(k2s[0]),
              ec._digits33(k1s[1]), ec._digits33(k2s[1]))
    negs = (n1s[0], n2s[0], n1s[1], n2s[1])
    rtab = tuple(jnp.zeros((16, n, 16), jnp.uint32) for _ in range(3))
    dig_want, neg_want, *_ = ec.pack_strauss_tab_inputs(digits, negs, rtab)

    dig_got, neg_got = glv_digits_np(np.asarray(u1), np.asarray(u2))
    np.testing.assert_array_equal(dig_got, np.asarray(dig_want)[:, :, :n])
    np.testing.assert_array_equal(neg_got, np.asarray(neg_want)[:, :n])


def test_recover_prelude_kernel_math():
    """_k_recover_prelude (numpy) vs the graph front of ecrecover_point:
    range checks, x-candidate, y^2 — value-for-value on valid rows and
    every invalid class (r=0, r>=N, s>=N, v>3, x>=P)."""
    from eges_tpu.ops import bigint, ec
    from eges_tpu.ops.bigint import FN, FP, N, NLIMBS, is_zero, select
    from eges_tpu.ops.pallas_kernels import _k_recover_prelude

    rows = [
        (rng.randrange(1, N), rng.randrange(1, N), 0),
        (rng.randrange(1, N), rng.randrange(1, N), 1),
        (rng.randrange(1, N), rng.randrange(1, N), 2),   # x = r + N path
        (rng.randrange(1, N), rng.randrange(1, N), 3),
        (0, rng.randrange(1, N), 0),                     # r = 0
        (N + 5, rng.randrange(1, N), 0),                 # r >= N
        (rng.randrange(1, N), N, 1),                     # s >= N
        (rng.randrange(1, N), rng.randrange(1, N), 7),   # bad v
        (P - N, 1, 2),                                   # r + N == P exactly
    ]
    r = jnp.asarray(np.stack([int_to_limbs(a % (1 << 256)) for a, _, _ in rows]))
    s = jnp.asarray(np.stack([int_to_limbs(b % (1 << 256)) for _, b, _ in rows]))
    v = jnp.asarray(np.asarray([c for _, _, c in rows], np.uint32))

    # graph reference (plain path ops on CPU)
    n_lim = jnp.broadcast_to(FN.m_limbs, r.shape)
    p_lim = jnp.broadcast_to(FP.m_limbs, r.shape)
    r_ok = (1 - is_zero(r)) * bigint.big_lt(r, n_lim)
    s_ok = (1 - is_zero(s)) * bigint.big_lt(s, n_lim)
    v_ok = (v < 4).astype(jnp.uint32)
    hi = (v >= 2).astype(jnp.uint32)
    x_wide = bigint.big_add(r, select(hi, n_lim, jnp.zeros_like(r)),
                            NLIMBS + 1)
    x_ok = is_zero(x_wide[..., NLIMBS:]) * bigint.big_lt(
        x_wide[..., :NLIMBS], p_lim)
    x_want = x_wide[..., :NLIMBS]
    y_sq_want = FP.add(FP.mul(FP.sqr(x_want), x_want), ec._const(7, x_want))
    ok_want = r_ok * s_ok * v_ok * x_ok

    x_got, ysq_got, ok_got = _k_recover_prelude(
        _t(r), _t(s), np.asarray(v), np)
    np.testing.assert_array_equal(_untq(x_got), np.asarray(x_want))
    np.testing.assert_array_equal(_untq(ysq_got), np.asarray(y_sq_want))
    np.testing.assert_array_equal(np.asarray(ok_got), np.asarray(ok_want))


def test_y_fix_kernel_math():
    """_k_y_fix vs the graph sqrt-check/canon/parity block, same root
    input on both sides (incl. a non-residue row where y_ok = 0)."""
    from eges_tpu.ops.bigint import FP
    from eges_tpu.ops.pallas_kernels import _k_y_fix

    vals = []
    while len(vals) < 3:  # quadratic residues
        c = rng.randrange(P)
        if pow(c, (P - 1) // 2, P) == 1:
            vals.append(c)
    nonres = next(c for c in range(2, 50)
                  if pow(c, (P - 1) // 2, P) == P - 1)
    vals.append(nonres)
    y_sq = jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))
    v = jnp.asarray(np.asarray([0, 1, 0, 1], np.uint32))
    root = FP.pow_const(y_sq, (P + 1) // 4)

    ok_want = FP.eq_mod(FP.sqr(root), y_sq)
    from eges_tpu.ops.bigint import select
    y0 = FP.canon(root)
    want_odd = (v & 1).astype(jnp.uint32)
    y_odd = (y0[..., 0] & 1).astype(jnp.uint32)
    y_want = select(want_odd ^ y_odd, FP.neg(y0), y0)

    y_got, ok_got = _k_y_fix(_t(root), _t(y_sq), np.asarray(v), np)
    np.testing.assert_array_equal(_untq(y_got), np.asarray(y_want))
    np.testing.assert_array_equal(np.asarray(ok_got), np.asarray(ok_want))


def test_u1u2_kernel_math():
    """_k_u1u2 vs the graph u1/u2 block (z reduction, r^-1 products)."""
    from eges_tpu.ops.bigint import FN, N
    from eges_tpu.ops.pallas_kernels import _k_u1u2

    n = 5
    zs = [rng.randrange(1 << 256) for _ in range(n)]
    ss = [rng.randrange(1, N) for _ in range(n)]
    rs = [rng.randrange(1, N) for _ in range(n)]
    z = jnp.asarray(np.stack([int_to_limbs(v) for v in zs]))
    s = jnp.asarray(np.stack([int_to_limbs(v) for v in ss]))
    r_inv = FN.inv_batched(jnp.asarray(np.stack([int_to_limbs(v)
                                                 for v in rs])))
    z_mod = FN.red(jnp.pad(z, ((0, 0), (0, 1))))
    u1_want = FN.neg(FN.mul(z_mod, r_inv))
    u2_want = FN.mul(s, r_inv)

    u1_got, u2_got = _k_u1u2(_t(z), _t(s), _t(r_inv), np)
    np.testing.assert_array_equal(_untq(u1_got), np.asarray(u1_want))
    np.testing.assert_array_equal(_untq(u2_got), np.asarray(u2_want))
    for zv, rv, row in zip(zs, rs, _untq(u1_got)):
        assert limbs_to_int(row) == (-zv * pow(rv, -1, N)) % N


def test_recover_finish_kernel_math():
    """_k_recover_finish vs to_affine + final selects + keccak word
    packing (incl. an infinity row and an ok=0 row)."""
    from eges_tpu.ops.bigint import FP, select
    from eges_tpu.ops.ec import to_affine
    from eges_tpu.ops.keccak_tpu import RATE
    from eges_tpu.ops.pallas_kernels import _k_recover_finish

    n = 5
    X, Y, Z = (np.asarray(t).copy() for t in _rand_point_batch(n))
    Z[2] = 0  # infinity row
    ok_in = np.asarray([1, 0, 1, 1, 1], np.uint32)
    Xj, Yj, Zj = (jnp.asarray(t) for t in (X, Y, Z))

    zi_raw = FP.pow_const(Zj, P - 2)  # relaxed, like the pow kernel leg
    inf = FP.is_zero_mod(Zj)
    zi = FP.canon(zi_raw)
    zi2 = FP.sqr(zi)
    x = FP.canon(FP.mul(Xj, zi2))
    y = FP.canon(FP.mul(Yj, FP.mul(zi, zi2)))
    zero = jnp.zeros_like(x)
    x = select(inf, zero, x)
    y = select(inf, zero, y)
    ok_want = jnp.asarray(ok_in) * (1 - inf)
    qx_want = select(ok_want, x, zero)
    qy_want = select(ok_want, y, zero)

    qx, qy, ok, words = _k_recover_finish(
        _t(Xj), _t(Yj), _t(Zj), _t(zi_raw), ok_in, np)
    np.testing.assert_array_equal(_untq(qx), np.asarray(qx_want))
    np.testing.assert_array_equal(_untq(qy), np.asarray(qy_want))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_want))

    # word packing vs the reference padding construction
    qx_i = [limbs_to_int(row) for row in _untq(qx)]
    qy_i = [limbs_to_int(row) for row in _untq(qy)]
    for i in range(n):
        msg = qx_i[i].to_bytes(32, "big") + qy_i[i].to_bytes(32, "big")
        buf = bytearray(RATE)
        buf[:64] = msg
        buf[64] ^= 0x01
        buf[RATE - 1] ^= 0x80
        want_words = np.frombuffer(bytes(buf), "<u4")
        got_words = np.asarray([w[i] for w in words], np.uint32)
        np.testing.assert_array_equal(got_words, want_words)


def test_addr_from_digest_rows():
    """The fused pipeline's address extraction (digest LE words 3..7 ->
    20 address bytes) against the host golden keccak."""
    from eges_tpu.crypto.keccak import keccak256
    from eges_tpu.crypto.verifier import addr_from_digest_rows

    msgs = [bytes(range(64)), rng.randbytes(64), b"\x00" * 64]
    B = len(msgs)
    dig = np.zeros((8, 256), np.uint32)  # padded wide like keccak_rows
    for i, m in enumerate(msgs):
        d = keccak256(m)
        dig[:, i] = np.frombuffer(d, "<u4")
    got = np.asarray(addr_from_digest_rows(jnp.asarray(dig), B))
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == keccak256(m)[12:], f"msg {i}"


def test_fused_pipeline_end_to_end_numpy():
    """The WHOLE fused recover pipeline, composed from every kernel's
    numpy twin exactly as ecrecover_point_fused wires the real kernels
    (prelude -> sqrt pow -> y-fix -> inv_n pow -> u1u2 -> glv digits ->
    R-table build + affine normalization -> self-gathering ladder ->
    inv_p pow -> finish -> keccak), checked against the independent
    host model: recovered addresses for valid rows, rejection for every
    invalid class.  This is the CPU-side proof of the fused WIRING, not
    just of each kernel's math in isolation."""
    from eges_tpu.crypto import secp256k1 as hostc
    from eges_tpu.crypto.keccak import keccak256
    from eges_tpu.ops.bigint import N
    from eges_tpu.ops.ec import GLV_BETA
    from eges_tpu.ops.pallas_kernels import (
        _k_cond_sub_p, _k_keccak_words, _k_mul, _k_recover_finish,
        _k_recover_prelude, _k_sqr, _k_u1u2, _k_unpack_be, _k_y_fix,
        glv_digits_np, point_table_np, pow_mod_np, strauss_tab_np,
    )

    # rows: valid signatures + one of each invalid class
    msgs, privs = [], []
    # randomized differential sweep: 24 fresh keys/messages (the fixed
    # module rng keeps it deterministic), which in practice covers both
    # recovery parities and a spread of scalar magnitudes
    B_valid = 24
    for _ in range(B_valid):
        msgs.append(rng.randrange(1 << 256).to_bytes(32, "big"))
        privs.append(rng.randrange(1, N).to_bytes(32, "big"))
    sigs, hashes = [], []
    for m, k in zip(msgs, privs):
        sigs.append(hostc.ecdsa_sign(m, k))  # 65 bytes r||s||v
        hashes.append(m)
    assert len({s[64] for s in sigs}) == 2, "want both v parities"
    # invalid rows: r=0, s>=N, v=9
    sigs.append(bytes(32) + sigs[0][32:])
    hashes.append(hashes[0])
    sigs.append(sigs[1][:32] + N.to_bytes(32, "big") + sigs[1][64:])
    hashes.append(hashes[1])
    sigs.append(sigs[2][:64] + bytes([9]))
    hashes.append(hashes[2])
    B = len(sigs)

    # wire bytes -> limb fields exactly as the prelude kernel unpacks
    srows = [np.asarray([sg[k] for sg in sigs], np.uint32)
             for k in range(65)]
    hrows = [np.asarray([h[k] for h in hashes], np.uint32)
             for k in range(32)]
    r_l = _k_unpack_be(srows, 0, np)
    s_l = _k_unpack_be(srows, 32, np)
    v = srows[64]
    z_l = _k_unpack_be(hrows, 0, np)

    def t(a):
        return [a[:, k].copy() for k in range(16)]

    # --- the fused wiring, numpy twins in ecrecover_point_fused order
    x, y_sq, ok0 = _k_recover_prelude(r_l, s_l, v, np)
    root = pow_mod_np(_untq(y_sq), (P + 1) // 4, "p")
    y, y_ok = _k_y_fix(t(root), y_sq, v, np)
    r_inv = pow_mod_np(_untq(r_l), N - 2, "n")
    u1, u2 = _k_u1u2(z_l, s_l, t(r_inv), np)

    dig, neg = glv_digits_np(_untq(u1), _untq(u2))
    xa, ya = _untq(x), _untq(y)
    tx, ty, tz = point_table_np(xa, ya)          # entries 2..15 Jacobian
    # affine normalization, mirroring _build_affine_table: entries 0
    # (infinity) and 1 (R itself) prepended, one inversion per entry
    ones = np.zeros((B, 16), np.uint32)
    ones[:, 0] = 1
    tx_full = np.concatenate([np.zeros((1, B, 16), np.uint32),
                              xa[None], tx])
    ty_full = np.concatenate([np.zeros((1, B, 16), np.uint32),
                              ya[None], ty])
    tz_full = np.concatenate([np.zeros((1, B, 16), np.uint32),
                              ones[None], tz])
    zi = pow_mod_np(tz_full.reshape(-1, 16), P - 2, "p")
    zi = _untq(_k_cond_sub_p(t(zi), np))         # inv_batched canonicalizes
    zi_l = t(zi)
    zi2 = _k_sqr(zi_l, np)
    tl = t(tx_full.reshape(-1, 16))
    ax = _k_mul(tl, zi2, np)
    ay = _k_mul(t(ty_full.reshape(-1, 16)), _k_mul(zi_l, zi2, np), np)
    beta = [np.full(16 * B, int(l), np.uint32)
            for l in int_to_limbs(GLV_BETA)]
    axb = _k_mul(ax, beta, np)

    def rows(limb_list):  # 16B-row limb list -> [256, B] table rows
        arr = _untq(limb_list).reshape(16, B, 16)
        return np.ascontiguousarray(arr.transpose(0, 2, 1)).reshape(-1, B)

    X, Y, Z = strauss_tab_np(dig, neg, rows(ax), rows(axb), rows(ay))
    zi_raw = pow_mod_np(_untq(Z).astype(np.uint32), P - 2, "p")
    qx, qy, ok, words = _k_recover_finish(
        X, Y, Z, t(zi_raw), ok0 * y_ok, np)
    digest = _k_keccak_words([w for w in words], np)
    dig_bytes = np.stack(digest, -1).astype("<u4").view(np.uint8) \
        .reshape(B, 32)

    # the packed block words must reproduce qx || qy as bytes — the
    # fused pubs output extracts them this way (verifier.words_to_bytes)
    import jax.numpy as _jnp

    from eges_tpu.crypto.verifier import words_to_bytes
    pub_bytes = np.asarray(words_to_bytes(
        _jnp.asarray(np.stack(words[:16])), B))
    for i in range(B):
        qx_i = limbs_to_int(_untq(qx)[i])
        qy_i = limbs_to_int(_untq(qy)[i])
        assert bytes(pub_bytes[i]) == (qx_i.to_bytes(32, "big")
                                       + qy_i.to_bytes(32, "big")), i

    # --- checks against the host model
    for i in range(B_valid):
        want = keccak256(hostc.privkey_to_pubkey(privs[i]))[12:]
        assert ok[i] == 1, f"valid row {i} rejected"
        assert bytes(dig_bytes[i][12:32]) == want, f"row {i} addr"
    for i in range(B_valid, B):
        assert ok[i] == 0, f"invalid row {i} accepted"


def test_rows8_layout_roundtrip():
    """The (8,128) re-lay helpers: _to_rows8/_from_rows8 are inverses
    and place batch b = blk*1024 + sublane*128 + lane at row
    limb*8 + sublane — the index contract the rows8 kernels read."""
    from eges_tpu.ops.pallas_kernels import _from_rows8, _to_rows8

    B = 2048
    a = jnp.asarray(np.arange(B * 16, dtype=np.uint32).reshape(B, 16))
    t = np.asarray(_to_rows8(a))
    assert t.shape == (2, 128, 128)
    for blk, s, l, k in ((0, 0, 0, 0), (0, 3, 17, 5), (1, 7, 127, 15),
                         (1, 2, 64, 8)):
        b = blk * 1024 + s * 128 + l
        assert t[blk, k * 8 + s, l] == np.asarray(a)[b, k], (blk, s, l, k)
    np.testing.assert_array_equal(np.asarray(_from_rows8(jnp.asarray(t), B)),
                                  np.asarray(a))
