"""AOT artifact store, prewarm, and double-buffered pipeline tests.

Toy graphs (a ``BatchVerifier._graph_fns`` override) drive the
IDENTICAL artifact machinery — export, serialize, header/integrity
check, deserialize, shared registry — in milliseconds, where the real
secp256k1 graphs take minutes of compile.  The store-level tests need
no verifier at all.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eges_tpu.crypto.aotstore import (AotStore, code_fingerprint,
                                      default_store,
                                      enable_persistent_cache)
from eges_tpu.crypto.verifier import BatchVerifier
from eges_tpu.utils.metrics import DEFAULT as metrics


# -- toy graphs: same (sigs, hashes[, pubs]) shapes as the real ones ------

def toy_recover(sigs, hashes):
    s = sigs.astype(jnp.uint32)
    h = hashes.astype(jnp.uint32)
    addrs = ((s[:, :20] * 3 + h[:, :20]) % 251).astype(jnp.uint8)
    pubs = jnp.zeros((sigs.shape[0], 64), jnp.uint8)
    ok = (s.sum(axis=1) + h.sum(axis=1)) % 2 == 0
    return addrs, pubs, ok


def toy_verify(sigs, hashes, pubs):
    s = sigs.astype(jnp.uint32)
    return (s.sum(axis=1) + hashes.astype(jnp.uint32).sum(axis=1)) % 2 == 0


class ToyVerifier(BatchVerifier):
    def _graph_fns(self):
        return {"recover": toy_recover, "verify": toy_verify}


def _rows(n):
    sigs = (np.arange(n * 65, dtype=np.uint32).reshape(n, 65)
            % 249).astype(np.uint8)
    hashes = (np.arange(n * 32, dtype=np.uint32).reshape(n, 32)
              % 247).astype(np.uint8)
    return sigs, hashes


# -- store-level ----------------------------------------------------------

def test_store_roundtrip(tmp_path):
    st = AotStore(str(tmp_path))
    payload = b"\x00stablehlo-bytes\xff" * 97
    path = st.save("recover", 16, "cpu:cpu", payload)
    assert os.path.exists(path)
    assert st.load("recover", 16, "cpu:cpu") == payload
    assert st.entries() == [os.path.basename(path)]
    # a different key is a plain miss, not an error
    before = metrics.counter("verifier.aot_load_errors").value
    assert st.load("recover", 32, "cpu:cpu") is None
    assert metrics.counter("verifier.aot_load_errors").value == before


def test_store_rejects_corruption(tmp_path):
    st = AotStore(str(tmp_path))
    path = st.save("recover", 16, "cpu:cpu", b"payload" * 50)
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0x40  # flip a payload byte behind the digest
    with open(path, "wb") as fh:
        fh.write(bytes(blob))
    before = metrics.counter("verifier.aot_load_errors").value
    assert st.load("recover", 16, "cpu:cpu") is None
    assert metrics.counter("verifier.aot_load_errors").value == before + 1


def test_store_rejects_version_and_code_rev_mismatch(tmp_path):
    versions = {"jax": "0.0.1", "jaxlib": "0.0.1"}
    writer = AotStore(str(tmp_path), fingerprint="a" * 16,
                      versions=versions)
    writer.save("recover", 16, "cpu:cpu", b"x" * 64)
    before = metrics.counter("verifier.aot_load_errors").value
    # same versions, different code rev -> rejected
    assert AotStore(str(tmp_path), fingerprint="b" * 16,
                    versions=versions).load("recover", 16,
                                            "cpu:cpu") is None
    # same code rev, different jaxlib -> rejected
    assert AotStore(str(tmp_path), fingerprint="a" * 16,
                    versions={"jax": "0.0.1", "jaxlib": "0.0.2"}
                    ).load("recover", 16, "cpu:cpu") is None
    # an intact artifact another build left is a miss, not an error:
    # the cache directory outlives a checkout
    assert metrics.counter("verifier.aot_load_errors").value == before
    # exact match -> loads
    assert AotStore(str(tmp_path), fingerprint="a" * 16,
                    versions=versions).load("recover", 16,
                                            "cpu:cpu") is not None


def test_default_store_knobs(tmp_path, monkeypatch):
    monkeypatch.setenv("EGES_AOT_DISABLE", "1")
    assert default_store() is None
    monkeypatch.delenv("EGES_AOT_DISABLE")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    st = default_store()
    assert st is not None and st.root == str(tmp_path / "aot")
    assert st.fingerprint == code_fingerprint()


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("placed", [True, False])
def test_cache_placement_follows_the_environment(tmp_path, monkeypatch,
                                                 placed):
    """One rule for the compile cache and the artifact store: where
    JAX_COMPILATION_CACHE_DIR is set both live under it and no code
    sets another directory; where it is not, fixed paths in the
    checkout — never a temporary or per-process one."""
    import tempfile

    from eges_tpu.crypto import aotstore

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want_cache, want_aot = str(tmp_path), str(tmp_path / "aot")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want_cache = os.path.join(_CHECKOUT, ".jax_cache")
        want_aot = os.path.join(_CHECKOUT, ".jax_aot")
    assert aotstore.cache_dir() == want_cache
    assert aotstore.aot_dir() == want_aot
    assert enable_persistent_cache() is True
    dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
    assert dirs == ([] if placed else [want_cache])
    if not placed:
        for d in (want_cache, want_aot):
            assert not d.startswith(tempfile.gettempdir())
            assert str(os.getpid()) not in d


def test_enable_persistent_cache_degrades(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("poisoned cache")

    monkeypatch.setattr(jax.config, "update", boom)
    before = metrics.counter("verifier.compile_cache_errors").value
    assert enable_persistent_cache() is False
    assert metrics.counter(
        "verifier.compile_cache_errors").value == before + 1


# -- prewarm: compile/save, load, registry, fall-through ------------------

def test_aot_prewarm_roundtrip_bit_identical(tmp_path):
    store = AotStore(str(tmp_path))
    sigs, hashes = _rows(10)

    v1 = ToyVerifier()
    info1 = v1.aot_prewarm(buckets=(16,), store=store)
    assert info1["aot_compiles"] == 1 and info1["aot_loads"] == 0
    assert store.entries(), "compile path must bank the artifact"
    a1, ok1 = v1.recover_addresses(sigs, hashes)

    # fresh process stand-in: empty registry, loads from the store
    v2 = ToyVerifier()
    info2 = v2.aot_prewarm(buckets=(16,), store=store)
    assert info2["aot_loads"] == 1 and info2["aot_compiles"] == 0
    st = v2.aot_stats()
    assert st["aot_loads"] == 1 and st["aot_compiles"] == 0
    # the prewarmed bucket is registered BEFORE any dispatch: no jit
    # recompile when real traffic arrives
    assert ("recover", 16) in v2._aot_execs
    assert 16 in v2._compiled_buckets

    a2, ok2 = v2.recover_addresses(sigs, hashes)
    assert (a1 == a2).all() and (ok1 == ok2).all()

    # ...and both match a fresh jit of the same graph bit-for-bit
    b = 16
    ps = np.zeros((b, 65), np.uint8)
    ph = np.zeros((b, 32), np.uint8)
    ps[:10], ph[:10] = sigs, hashes
    ref_a, _, ref_ok = jax.jit(toy_recover)(jnp.asarray(ps),
                                            jnp.asarray(ph))
    assert (np.asarray(ref_a)[:10] == a2).all()
    assert (np.asarray(ref_ok)[:10].astype(bool) == ok2).all()


def test_aot_prewarm_dedup_and_verify_op(tmp_path):
    store = AotStore(str(tmp_path))
    v = ToyVerifier()
    info = v.aot_prewarm(buckets=(16, 16, 15), store=store,
                         ops=("recover", "verify"))
    # 15 rounds to the same 16-bucket; both ops warm exactly once each
    assert info["buckets"] == [16]
    assert info["aot_compiles"] == 2
    # a second prewarm is a registry no-op (the mesh-lane dedup path)
    again = v.aot_prewarm(buckets=(16,), store=store,
                          ops=("recover", "verify"))
    assert again["aot_loads"] == 0 and again["aot_compiles"] == 0

    sigs, hashes = _rows(12)
    pubs = np.zeros((12, 64), np.uint8)
    got = v.verify(sigs, hashes, pubs)
    want = np.asarray(jax.jit(toy_verify)(
        jnp.asarray(np.pad(sigs, ((0, 4), (0, 0)))),
        jnp.asarray(np.pad(hashes, ((0, 4), (0, 0)))),
        jnp.asarray(np.zeros((16, 64), np.uint8)))).astype(bool)
    assert (got == want[:12]).all()


def test_corrupted_artifact_falls_through_to_compile(tmp_path):
    store = AotStore(str(tmp_path))
    v1 = ToyVerifier()
    v1.aot_prewarm(buckets=(16,), store=store)
    path = store.path_for("recover", 16, v1.device_kind)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as fh:
        fh.write(bytes(blob))

    v2 = ToyVerifier()
    info = v2.aot_prewarm(buckets=(16,), store=store)
    # BENCH_r02 contract: degrade (recompile), never crash
    assert info["aot_loads"] == 0 and info["aot_compiles"] == 1
    sigs, hashes = _rows(8)
    a1, ok1 = v1.recover_addresses(sigs, hashes)
    a2, ok2 = v2.recover_addresses(sigs, hashes)
    assert (a1 == a2).all() and (ok1 == ok2).all()
    # the recompile re-banked a GOOD artifact
    v3 = ToyVerifier()
    assert v3.aot_prewarm(buckets=(16,), store=store)["aot_loads"] == 1


# -- cluster restart: prewarm from artifacts, journal the timing ----------

def test_cluster_restart_prewarms_from_store(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # bank the artifact the way a previous process would have
    seed = ToyVerifier()
    seed.aot_prewarm(buckets=(16,))

    from eges_tpu.sim.cluster import SimCluster

    c = SimCluster(3, signed=False, verifier=ToyVerifier())
    c.start()
    c.run(2.0)
    c.crash(0)
    c.restart(0)

    backing = c.verifier._verifier
    st = backing.aot_stats()
    assert st["aot_loads"] >= 1, st
    assert st["aot_compiles"] == 0, \
        "prewarmed bucket must not recompile on restart"
    evs = [e for e in c.nodes[0].node.journal.events()
           if e["type"] == "verifier_aot_load"]
    assert evs and evs[-1]["aot_loads"] >= 1
    assert evs[-1].get("restart") is True
    assert evs[-1]["cold_start_s"] >= 0.0


# -- double-buffered window pipeline --------------------------------------

def _slow_pipelined(delay_s: float):
    import time

    from eges_tpu.crypto.verify_host import PipelinedNativeVerifier

    class Slow(PipelinedNativeVerifier):
        def recover_addresses(self, sigs, hashes):
            time.sleep(delay_s)
            return super().recover_addresses(sigs, hashes)

    return Slow()


def _signed_entries(n):
    from eges_tpu.crypto import native
    from eges_tpu.crypto import secp256k1 as host

    out = []
    for i in range(n):
        msg = (i + 1).to_bytes(4, "big") * 8
        priv = bytes([(i % 200) + 11]) * 32
        sig = (native.ec_sign(msg, priv) if native.available()
               else host.ecdsa_sign(msg, priv))
        out.append((msg, sig, host.pubkey_to_address(
            host.privkey_to_pubkey(priv))))
    return out


def test_pipelined_scheduler_matches_host_and_overlaps():
    from eges_tpu.crypto.scheduler import VerifierScheduler

    entries = _signed_entries(96)
    sched = VerifierScheduler(_slow_pipelined(0.01), window_ms=1.0,
                              max_batch=16)
    try:
        futs = [(sched.submit(h, s), addr) for h, s, addr in entries]
        for f, addr in futs:
            assert f.result(60) == addr
    finally:
        sched.close()
    st = sched.stats()
    assert st["pipeline_windows"] > 0
    # a deep queue over a slow lane MUST overlap: window N+1 stages
    # while window N computes
    assert st["pipeline_overlapped"] >= 1
    assert 0.0 < st["pipeline_overlap_ratio"] <= 1.0
    assert st["devices"][0]["pipeline_overlap_ratio"] == \
        st["pipeline_overlap_ratio"]


def test_pipelined_failure_surfaces_at_collect():
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import PipelinedNativeVerifier

    v = PipelinedNativeVerifier()
    calls = {"n": 0}

    def hook(n):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected device fault")

    v.failure_hook = hook
    sched = VerifierScheduler(v, window_ms=1.0, max_batch=16)
    try:
        entries = _signed_entries(48)
        futs = [(sched.submit(h, s), addr) for h, s, addr in entries]
        # every future resolves: the failed window diverts to the host
        # path (per-lane breaker), later windows flow normally
        for f, addr in futs:
            assert f.result(60) == addr
    finally:
        sched.close()
    # the hook fired exactly once per window it killed (stage_recover
    # must not double-invoke it)
    assert calls["n"] >= 1


def test_inline_path_untouched_for_plain_verifier():
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=1.0,
                              max_batch=16)
    try:
        entries = _signed_entries(24)
        futs = [(sched.submit(h, s), addr) for h, s, addr in entries]
        for f, addr in futs:
            assert f.result(60) == addr
    finally:
        sched.close()
    st = sched.stats()
    # no split-phase target -> no pipelined windows, determinism intact
    assert st["pipeline_windows"] == 0
    assert st["pipeline_overlap_ratio"] == 0.0


# -- mesh: every lane's device warm before it serves ------------------------

def test_mesh_prewarm_leaves_no_compile_to_a_lanes_first_window(tmp_path):
    """``MeshBatchVerifier.aot_prewarm`` fills the shared registry from
    the default device and then runs, on every lane's device, each
    bucket a lane can be handed of a ``max(buckets)``-row window (the
    scheduler's chunk cap): after it, a lane's first window of such a
    bucket compiles nothing, where without the lane warm it compiles
    once a device.  A larger bucket stays device 0's alone."""
    import jax.monitoring as mon

    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verifier import MeshBatchVerifier

    class ToyMesh(MeshBatchVerifier):
        def _graph_fns(self):
            return {"recover": toy_recover, "verify": toy_verify}

    compiles = []

    def on(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    mon.register_event_duration_secs_listener(on)
    devs = jax.devices()[:4]
    assert len(devs) == 4  # tests/conftest.py forces eight host devices
    mesh = jax.sharding.Mesh(np.array(devs), ("dp",))
    v = ToyMesh(mesh=mesh, min_bucket=16)
    sched = VerifierScheduler(v, window_ms=10_000.0, max_batch=64)
    try:
        info = v.aot_prewarm(buckets=(16, 32, 64),
                             store=AotStore(str(tmp_path)))
        assert info["aot_compiles"] == 3
        # 64 rows over four lanes: chunks of 16, so of (16, 32, 64) only
        # the 16 bucket is a lane's; the scheduler computes the same cap
        assert info["lane_buckets"] == [16]
        assert sched._chunk_cap == 16
        sigs, hashes = _rows(16)
        want = v.device_targets()[0].recover_addresses(sigs, hashes)
        n0 = len(compiles)
        for t in v.device_targets():
            st = t.commit_recover(t.stage_recover(sigs, hashes))
            assert {d for a in st.out for d in a.devices()} == {t.device}
            got = t.collect_recover(st)
            assert np.array_equal(got[0], want[0])
            assert np.array_equal(got[1], want[1])
        assert len(compiles) == n0, "a lane's first window compiled"
        # the control: a bucket no lane was warmed for compiles on the
        # first lane that is handed it
        big = _rows(32)
        t = v.device_targets()[3]
        t.collect_recover(t.commit_recover(t.stage_recover(*big)))
        assert len(compiles) > n0
    finally:
        sched.close()
        # jax.monitoring has no public unregister; the listener only
        # appends to a list nobody reads after this
