"""Ring-collective tests on the 8-virtual-device CPU mesh: the ring
tally must equal psum bitwise, and the ring gather must reassemble all
rows on every device (ref role: the on-device vote fan-in of
core/geec_state.go:1184-1227, laid out for nearest-neighbor ICI)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eges_tpu.parallel import data_parallel_mesh, shard_rows
from eges_tpu.parallel.ring import ring_gather, ring_tally


def _mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices (conftest sets them up)")
    return data_parallel_mesh(devs[:8])


def _toy(rows):
    # a stand-in row kernel: "ok" = parity of the row sum
    def fn(x):
        ok = (jnp.sum(x, axis=-1) % 2).astype(jnp.uint32)
        return x * 2, ok

    return fn


def test_ring_tally_matches_psum():
    mesh = _mesh()
    x = np.arange(16 * 8, dtype=np.uint32).reshape(16 * 8 // 16, 16)  # [8,16]
    x = np.tile(x, (2, 1))  # 16 rows over 8 devices -> 2 rows each
    fn = _toy(x.shape[0])

    ringed = ring_tally(fn, mesh, "dp", n_in=1, n_out=2, tally_out=1)
    psummed = shard_rows(fn, mesh, "dp", n_in=1, n_out=2, tally_out=1)
    xr, okr, tally_r = ringed(jnp.asarray(x))
    xp, okp, tally_p = psummed(jnp.asarray(x))
    assert int(tally_r) == int(tally_p) == int(np.asarray(okp).sum())
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xp))


def test_ring_gather_reassembles_all_rows():
    mesh = _mesh()
    x = np.arange(24 * 16, dtype=np.uint32).reshape(24, 16)
    fn = _toy(24)
    gathered_fn = ring_gather(lambda a: fn(a)[0], mesh, "dp", n_in=1)
    out = np.asarray(gathered_fn(jnp.asarray(x)))
    np.testing.assert_array_equal(out, x * 2)


@pytest.mark.slow
def test_ring_tally_on_real_ecrecover_shard():
    """The actual verify kernel under the ring tally (tiny batch)."""
    import secrets

    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.verifier import ecrecover_batch

    mesh = _mesh()
    rows = 8
    sigs = np.zeros((rows, 65), np.uint8)
    hashes = np.zeros((rows, 32), np.uint8)
    for i in range(rows):
        msg = secrets.token_bytes(32)
        priv = bytes([i + 3]) * 32
        sigs[i] = np.frombuffer(host.ecdsa_sign(msg, priv), np.uint8)
        hashes[i] = np.frombuffer(msg, np.uint8)
    fn = ring_tally(ecrecover_batch, mesh, "dp", n_in=2, n_out=3,
                    tally_out=2)
    addrs, pubs, ok, tally = fn(jnp.asarray(sigs), jnp.asarray(hashes))
    assert int(tally) == rows
    assert np.asarray(ok).all()


def test_collective_is_psum_unless_told_otherwise_in_code(monkeypatch):
    """No perf artefact or environment value steers the full-mesh
    tally: psum by default, ring only when the caller builds the
    verifier that way, anything else refused."""
    import pytest

    from eges_tpu.crypto.verifier import BatchVerifier

    mesh = data_parallel_mesh(jax.devices()[:4])
    monkeypatch.setenv("EGES_MESH_COLLECTIVE", "ring")  # ignored now
    assert BatchVerifier(mesh=mesh).collective_for(1024) == "psum"
    assert BatchVerifier(mesh=mesh,
                         collective="ring").collective_for(1024) == "ring"
    assert BatchVerifier().collective_for(1024) == "none"
    one = data_parallel_mesh(jax.devices()[:1])
    assert BatchVerifier(mesh=one,
                         collective="ring").collective_for(16) == "psum"
    with pytest.raises(ValueError):
        BatchVerifier(mesh=mesh, collective="auto")


def test_all_to_all_resplit_roundtrip():
    """Row-sharded -> feature-sharded -> fn -> row-sharded equals the
    unsharded computation (the Ulysses-style layout swap)."""
    from eges_tpu.parallel.ring import all_to_all_resplit

    mesh = _mesh()
    rows, feat = 16, 64  # feat divides 8 devices
    x = np.arange(rows * feat, dtype=np.uint32).reshape(rows, feat)

    def fn(a):
        # a cross-row transform on the feature shard: every device sees
        # ALL rows for its slice, so a row-axis reduction is local
        return a + a.sum(axis=0, keepdims=True).astype(np.uint32)

    wrapped = all_to_all_resplit(fn, mesh, "dp", n_in=1)
    got = np.asarray(wrapped(jnp.asarray(x)))
    want = x + x.sum(axis=0, keepdims=True, dtype=np.uint32)
    np.testing.assert_array_equal(got, want)
