"""Provenance stamp shared by the profiling artifacts.

The continuous profiling plane (``eges_tpu/utils/profiler.py``) leads
every artifact with one header, so two artifacts from different
checkouts are distinguishable::

    # eges-profile-v1 {"git_rev": ..., "platform_detail": ..., ...}

Stdlib-only; ``jax`` is never imported here, so header/provenance
consumers (the node service's periodic ``profile.folded`` dump) stay
JAX-free.
"""

from __future__ import annotations

import json
import os
import platform
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_rev() -> str | None:
    """Current commit hash straight from ``.git`` (no subprocess — the
    harnesses stay import-light and a missing git binary must not fail
    a measurement)."""
    try:
        head = os.path.join(_REPO, ".git", "HEAD")
        with open(head, "r", encoding="utf-8") as fh:
            ref = fh.read().strip()
        if ref.startswith("ref: "):
            with open(os.path.join(_REPO, ".git", *ref[5:].split("/")),
                      "r", encoding="utf-8") as fh:
                return fh.read().strip()[:40] or None
        return ref[:40] or None
    except OSError:
        return None


def _mod_version(name: str) -> str | None:
    """Version of an ALREADY-IMPORTED module — a provenance helper must
    never be the thing that drags jax into a process."""
    mod = sys.modules.get(name)
    if mod is None:
        return None
    v = getattr(mod, "__version__", None)
    return str(v) if v is not None else None


def artifact_header(**extra) -> dict:
    """The shared provenance stamp: platform detail, git revision,
    python + jax/jaxlib versions (when loaded), plus caller extras."""
    hdr = {
        "platform_detail": "%s-%s" % (sys.platform, platform.machine()),
        "python": platform.python_version(),
        "git_rev": git_rev(),
        "jax": _mod_version("jax"),
        "jaxlib": _mod_version("jaxlib"),
    }
    hdr.update(extra)
    return hdr


def header_line(**extra) -> str:
    """The header as the one-line ``# eges-profile-v1`` comment every
    profiling artifact leads with."""
    return ("# eges-profile-v1 "
            + json.dumps(artifact_header(**extra), sort_keys=True))
