"""Versioned on-disk store for AOT-serialized verifier executables.

The compile tax this layer kills: every (op, bucket) recover/verify
graph costs a fresh trace + XLA compile per process — about two
minutes of Python tracing and a quarter of a minute of compiling per
bucket on the kernel path — so every cold node, and every
chaos-restarted node, serves its first minutes at host-fallback
throughput.  ``jax.export`` lowers a jitted graph once, serializes the
StableHLO module, and any later process deserializes it in milliseconds
and skips the trace/lower half entirely (the XLA backend-compile half
then hits the persistent compilation cache, which keys on the identical
HLO).  This module owns the artifact files; the compile/load policy
lives in :meth:`eges_tpu.crypto.verifier.BatchVerifier.aot_prewarm`.

Artifacts are keyed by ``(op, bucket, device-kind)`` and guarded by a
versioned header carrying the jax/jaxlib versions and a code-revision
fingerprint (a hash over the graph-defining sources), plus a sha256
integrity digest of the payload.  ANY mismatch — torn file, corrupted
payload, different jaxlib ABI, edited kernel source, different device
kind — makes :meth:`AotStore.load` return ``None`` so the caller falls
through to a normal jit compile: a poisoned cache must degrade, never
crash.

Placement (one rule for the persistent compile cache and the artifact
store, :func:`cache_dir` / :func:`aot_dir`): where
``JAX_COMPILATION_CACHE_DIR`` is set, jax's cache lives there and the
artifacts in ``<that dir>/aot``, and no code sets another; where it is
not, ``<checkout>/.jax_cache`` and ``<checkout>/.jax_aot``.  Never a
temporary or per-process path — the path is part of jax's cache key,
so a directory that moves never hits.

``EGES_AOT_DISABLE=1`` disables the store entirely
(:func:`default_store` returns ``None``; every consumer treats that as
"compile like before").

This module must stay importable WITHOUT JAX (the bench parent and
host-fallback processes import the scheduler stack, which may reach
here); jax is only touched inside :func:`runtime_versions` /
:func:`enable_persistent_cache`, lazily.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import tempfile

_MAGIC = b"EGESAOT1"

# sources whose edits invalidate every serialized executable: the graph
# definitions and everything they lower through
_FINGERPRINT_SOURCES = (
    "ops/bigint.py", "ops/ec.py", "ops/keccak_tpu.py",
    "ops/pallas_kernels.py", "crypto/verifier.py", "crypto/bucketing.py",
)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def code_fingerprint() -> str:
    """sha256 over the graph-defining module sources — the ``code_rev``
    half of the artifact key.  A missing file hashes as its name only,
    so a trimmed install still produces a stable (if weaker) rev."""
    h = hashlib.sha256()
    pkg = os.path.join(_repo_root(), "eges_tpu")
    for rel in _FINGERPRINT_SOURCES:
        h.update(rel.encode())
        try:
            with open(os.path.join(pkg, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            pass
    return h.hexdigest()[:16]


def runtime_versions() -> dict:
    """The jax/jaxlib version pair baked into every artifact header; a
    jax-free process reports ``none`` (its artifacts would never load
    anywhere, but it also never saves any)."""
    try:
        import jax

        jaxlib = getattr(jax, "lib", None)
        # the x64 flag is an ABI dimension too: an artifact exported
        # under jax_enable_x64 has 64-bit dtypes baked into its
        # signature, and loading it into a 32-bit process (or vice
        # versa) would dtype-mismatch at call time — key it so the
        # load path degrades to a recompile instead
        return {"jax": getattr(jax, "__version__", "none"),
                "jaxlib": getattr(jaxlib, "version", None)
                and jaxlib.version.__version__ or "none",
                "x64": "1" if jax.config.jax_enable_x64 else "0"}
    # analysis: allow-swallow(no jax in this process: version-less
    # headers simply never match, the load path degrades to recompile)
    except Exception:
        return {"jax": "none", "jaxlib": "none", "x64": "none"}


def _safe(part: str) -> str:
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in part)


class AotStore:
    """One directory of ``<op>_b<bucket>_<device-kind>.aot`` artifacts.

    File format: ``EGESAOT1`` magic, a u32 header length, the header
    JSON (versions, device kind, op, bucket, code rev, payload sha256 +
    length), then the ``jax.export`` payload.  Writes are atomic
    (tempfile + rename) so a crashed writer leaves no torn artifact
    under the key — a torn temp file is never looked at.
    """

    def __init__(self, root: str, fingerprint: str | None = None,
                 versions: dict | None = None):
        self.root = root
        self.fingerprint = fingerprint or code_fingerprint()
        self.versions = dict(versions or runtime_versions())

    def path_for(self, op: str, bucket: int, device_kind: str) -> str:
        return os.path.join(
            self.root, f"{_safe(op)}_b{int(bucket)}_"
                       f"{_safe(device_kind)}.aot")

    def _header(self, op: str, bucket: int, device_kind: str,
                payload: bytes) -> dict:
        return {"format": 1, "op": op, "bucket": int(bucket),
                "device_kind": device_kind,
                "code_rev": self.fingerprint,
                "jax": self.versions.get("jax", "none"),
                "jaxlib": self.versions.get("jaxlib", "none"),
                "x64": self.versions.get("x64", "none"),
                "payload_len": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest()}

    def save(self, op: str, bucket: int, device_kind: str,
             payload: bytes) -> str:
        """Atomically write one artifact; returns its path."""
        from eges_tpu.utils.metrics import DEFAULT as metrics

        os.makedirs(self.root, exist_ok=True)
        header = json.dumps(self._header(op, bucket, device_kind, payload),
                            sort_keys=True).encode()
        path = self.path_for(op, bucket, device_kind)
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(_MAGIC)
                fh.write(struct.pack("<I", len(header)))
                fh.write(header)
                fh.write(payload)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        metrics.counter("verifier.aot_saves").inc()
        return path

    def load(self, op: str, bucket: int, device_kind: str) -> bytes | None:
        """The serialized payload for one key, or ``None`` on ANY
        mismatch — callers fall through to a fresh jit compile, they
        never crash on a bad artifact.  A missing file, or an intact
        artifact another build left (a different code rev, jax/jaxlib
        or x64 setting — the cache directory outlives a checkout), is a
        plain miss; a bad magic, a torn or corrupted payload, or a file
        under the wrong key counts ``verifier.aot_load_errors``."""
        path = self.path_for(op, bucket, device_kind)
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError:
            return None
        want = self._header(op, bucket, device_kind, b"")
        try:
            if blob[:8] != _MAGIC:
                raise ValueError("bad magic")
            (hlen,) = struct.unpack("<I", blob[8:12])
            header = json.loads(blob[12:12 + hlen])
            payload = blob[12 + hlen:]
            for key in ("format", "op", "bucket", "device_kind"):
                if header.get(key) != want[key]:
                    raise ValueError(
                        f"{key} mismatch: artifact has "
                        f"{header.get(key)!r}, runtime wants {want[key]!r}")
            if header.get("payload_len") != len(payload):
                raise ValueError("payload length mismatch (torn write?)")
            if header.get("sha256") != hashlib.sha256(payload).hexdigest():
                raise ValueError("payload digest mismatch (corruption)")
            stale = [key for key in ("code_rev", "jax", "jaxlib", "x64")
                     if header.get(key) != want[key]]
            if stale:
                from eges_tpu.utils.log import get_logger

                get_logger("geec.aot").info(
                    "aot artifact from another build; recompiling",
                    path=path, differs=",".join(stale))
                return None
            return payload
        # analysis: allow-swallow(a stale/corrupted artifact degrades to
        # a normal jit compile; the error is
        # logged + counted, the caller sees a plain cache miss)
        except Exception as e:
            from eges_tpu.utils.log import get_logger
            from eges_tpu.utils.metrics import DEFAULT as metrics

            metrics.counter("verifier.aot_load_errors").inc()
            get_logger("geec.aot").warn(
                "aot artifact rejected; falling through to jit",
                path=path, err=str(e))
            return None

    def entries(self) -> list[str]:
        """Artifact file names currently in the store (diagnostics)."""
        try:
            return sorted(f for f in os.listdir(self.root)
                          if f.endswith(".aot"))
        except OSError:
            return []


def cache_dir() -> str:
    """Where jax's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
    ``<checkout>/.jax_cache``.  Every launcher and test goes through
    this one helper."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_repo_root(), ".jax_cache"))


def aot_dir() -> str:
    """The artifact store's directory, beside the compile cache:
    ``<JAX_COMPILATION_CACHE_DIR>/aot`` when the variable is set, else
    ``<checkout>/.jax_aot``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return (os.path.join(placed, "aot") if placed
            else os.path.join(_repo_root(), ".jax_aot"))


def default_store() -> AotStore | None:
    """The process-default store at :func:`aot_dir`; ``None`` when
    disabled (consumers then compile exactly as before this layer)."""
    if os.environ.get("EGES_AOT_DISABLE") == "1":
        return None
    return AotStore(aot_dir())


def enable_persistent_cache(min_compile_s: float = 2.0) -> bool:
    """Turn on jax's persistent compilation cache at :func:`cache_dir`.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it
    and this sets no directory at all.  Any error (an unwritable
    directory, a poisoned cache implementation) is logged via
    ``utils.log``, counted in ``verifier.compile_cache_errors``, and
    the process continues WITHOUT the cache instead of taking the
    backend down.  Returns True when the cache was configured."""
    from eges_tpu.utils.log import get_logger
    from eges_tpu.utils.metrics import DEFAULT as metrics

    try:
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_s))
        return True
    # analysis: allow-swallow(a broken persistent cache must degrade to
    # uncached compiles, never poison the backend)
    except Exception as e:
        metrics.counter("verifier.compile_cache_errors").inc()
        get_logger("geec.aot").warn(
            "persistent compile cache unavailable; continuing without",
            dir=cache_dir(), err=str(e))
        return False
