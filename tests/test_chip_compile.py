"""The main-path Pallas kernels compiled by Mosaic for a DESCRIBED v5e.

The TPU's compiler is installed here even though no chip is attached:
``get_topology_desc`` describes a ``v5e:2x2`` host and ``jit(...).lower
(shapes).compile()`` then raises whatever the chip's compiler would
raise — a slice not aligned to the tiling, more fast memory than a
kernel may use, a kernel that cannot be lowered at all — at no chip
time.  Nothing runs, so this says nothing about results (those are
``chip_smoke.py``'s business on the chip, and the numpy/interpret twins'
in ``tests/test_pallas_kernels.py``).

Rules of this file (``on-chip-measurement`` guide §2): it is the ONLY
test file that describes a topology; the description happens inside the
module-scoped ``topo`` fixture, never at import, never in a ``skipif``
or ``parametrize`` argument; the fixture is not ``autouse`` and does not
live in ``conftest.py``; every compile happens in the test's own
process, with the persistent compile cache turned off around it (an
executable compiled for a described chip cannot be read back without
one).

The tests compile the kernels ONE BY ONE at the served path's 1024-row
bucket.  Mosaic takes a second or two for any of them; what varies is
the Python tracing of the kernel BODY (every in-kernel limb operation is
a ``jnp`` operator call): the stage kernels trace in 0.1-4 s and stay in
tier-1, the five loop kernels (``strauss_tab`` 55 s, ``pow_mod`` 13-27 s,
``glv_digits`` 17 s, ``point_table`` 12 s, measured here) ride ``slow``
with the whole recover graph, which traces for about two minutes.
"""

import os

import jax
import jax.numpy as jnp
import pytest

ROWS = 1024  # the bucket the served path caps at (scheduler max_batch)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def uncached():
    """Persistent compile cache off around a described-chip compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` over ``(shape, dtype)`` arguments placed on the
    described chip and compile it; returns the compiled text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _limbs(rows=ROWS, limbs=16):
    return ((rows, limbs), jnp.uint32)


def _kernel_cases():
    """name -> (fn, shapes): each main-path kernel at 1024 rows with
    ``interpret=False`` (the gates key on the CPU backend here, so the
    tests pick the Mosaic path themselves)."""
    from eges_tpu.ops import pallas_kernels as pk
    from eges_tpu.ops.bigint import N, P

    wide = -(-ROWS // pk.LANE_BLOCK) * pk.LANE_BLOCK
    u32 = jnp.uint32
    return {
        "recover_prelude": (
            lambda s, h: pk.recover_prelude_pallas(s, h, interpret=False),
            [((ROWS, 65), jnp.uint8), ((ROWS, 32), jnp.uint8)]),
        "pow_mod_p": (
            lambda a: pk.pow_mod_pallas(a, (P + 1) // 4, "p",
                                        interpret=False),
            [_limbs()]),
        "pow_mod_n": (
            lambda a: pk.pow_mod_pallas(a, N - 2, "n", interpret=False),
            [_limbs()]),
        "y_fix": (
            lambda r, y, v: pk.y_fix_pallas(r, y, v, interpret=False),
            [_limbs(), _limbs(), ((ROWS,), u32)]),
        "u1u2": (
            lambda z, s, ri: pk.u1u2_pallas(z, s, ri, interpret=False),
            [_limbs(), _limbs(), _limbs()]),
        "glv_digits": (
            lambda a, b: pk.glv_digits_pallas(a, b, interpret=False),
            [_limbs(), _limbs()]),
        "point_table": (
            lambda x, y: pk.point_table_pallas(x, y, interpret=False),
            [_limbs(), _limbs()]),
        "strauss_tab": (
            lambda d, n, tx, tl, ty: pk.strauss_tab(
                d, n, tx, tl, ty, ROWS, interpret=False),
            [((33, 8, wide), u32), ((8, wide), u32), ((256, wide), u32),
             ((256, wide), u32), ((256, wide), u32)]),
        "recover_finish": (
            lambda X, Y, Z, zi, ok: pk.recover_finish_pallas(
                X, Y, Z, zi, ok, interpret=False),
            [_limbs(), _limbs(), _limbs(), _limbs(), ((ROWS,), u32)]),
        "keccak_rows": (
            lambda w: pk.keccak_rows_pallas(w, interpret=False),
            [((34, wide), u32)]),
        "ew_glue_fp_sub": (
            lambda a, b: pk.fp_sub_pallas(a, b, interpret=False),
            [_limbs(), _limbs()]),
    }


# names only: the cases (and anything touching jax backends) are built
# inside the test, after the fixture has described the topology
_STAGE_KERNELS = ("recover_prelude", "y_fix", "u1u2", "recover_finish",
                  "keccak_rows", "ew_glue_fp_sub")
_LOOP_KERNELS = ("pow_mod_p", "pow_mod_n", "glv_digits", "point_table",
                 "strauss_tab")
_KERNELS = _STAGE_KERNELS + _LOOP_KERNELS


@pytest.mark.parametrize(
    "name", list(_STAGE_KERNELS)
    + [pytest.param(n, marks=pytest.mark.slow) for n in _LOOP_KERNELS])
def test_kernel_compiles_for_v5e(name, one_chip, uncached):
    fn, shapes = _kernel_cases()[name]
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the HLO"


def test_kernel_case_list_is_complete():
    assert set(_kernel_cases()) == set(_KERNELS)


def test_keccak_grid_variant_compiles_for_v5e(one_chip, uncached,
                                              monkeypatch):
    """The round-per-grid-step keccak (``EGES_TPU_KECCAK_GRID=1``, off
    by default) exists to hand Mosaic a 24x smaller body: what matters
    about it before a chip A/B is that Mosaic accepts its
    program_id/when/state-carry structure — compiled here in seconds,
    where its interpret-mode run took the suite's longest minutes."""
    from eges_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("EGES_TPU_KECCAK_GRID", "1")
    assert pk.keccak_grid_enabled()
    text = _compile(lambda w: pk.keccak_rows_pallas(w, interpret=False),
                    one_chip, ((34, ROWS), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.slow
def test_whole_recover_graph_compiles_for_v5e(one_chip, uncached,
                                              monkeypatch):
    """``ecrecover_batch`` on the kernel path at 1024 rows: about two
    minutes of tracing and a quarter of a minute of compiling.  The two
    gates key on ``jax.default_backend()`` (the CPU here), so the test
    steers them itself."""
    from eges_tpu.crypto.verifier import ecrecover_batch
    from eges_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "ladder_kernels_enabled", lambda: True)
    monkeypatch.setattr(pk, "_default_interpret", lambda: False)
    text = _compile(ecrecover_batch, one_chip,
                    ((ROWS, 65), jnp.uint8), ((ROWS, 32), jnp.uint8))
    assert text.count("tpu_custom_call") >= 10
