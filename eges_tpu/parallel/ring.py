"""Ring collectives over the device mesh (ICI-riding, ppermute-based).

The long-context/sequence-parallel story of this domain (SURVEY §5
"long-context"): the scaling axis is the signature batch, and the
multi-chip layouts are

* **row-sharding + psum** — the default (`shard_rows`), one tree
  all-reduce for the ACK tally;
* **ring reduce** (this module) — the tally circulates the ring with
  `lax.ppermute`, the ring-attention communication pattern applied to
  the verify pipeline: each hop overlaps a neighbor exchange with local
  work, which on real hardware keeps traffic on nearest-neighbor ICI
  links instead of a global tree (the mental model of the public
  scaling-book recipe: pick a mesh, lay shardings so collectives ride
  ICI, let XLA schedule);
* **ring gather** — every device ends with the full result row-set
  (all-gather built from N-1 neighbor hops), for the follower path
  where every node wants every verdict.

On this permissioned chain these replace the reference's vote fan-in
over UDP (ref: core/geec_state.go:1184-1227 handleVerifyReplies) when
the tally happens ON-DEVICE across chips.
"""

from __future__ import annotations

import functools

import numpy as np


def ring_perm(n: int) -> list[tuple[int, int]]:
    """The +1 ring permutation for an ``n``-device axis."""
    return [(i, (i + 1) % n) for i in range(n)]


def _shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check off: a ring accumulation is
    replicated by construction (every device sums the same N pieces),
    but the static varying-axes analysis cannot see through the
    ppermute chain."""
    import inspect

    from eges_tpu.parallel import shard_map_fn

    smap = shard_map_fn()
    kw = {}
    params = inspect.signature(smap).parameters
    if "check_vma" in params:
        kw["check_vma"] = False
    elif "check_rep" in params:
        kw["check_rep"] = False
    return smap(fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, **kw)


@functools.lru_cache(maxsize=None)
def ring_tally(fn, mesh, axis: str = "dp", *, n_in: int, n_out: int,
               tally_out: int):
    """Like :func:`~eges_tpu.parallel.shard_rows` but the tally is a
    RING all-reduce: N-1 `ppermute` hops, each adding the neighbor's
    partial sum — bitwise-identical result to `psum`, nearest-neighbor
    traffic pattern.

    Memoized on ``(fn, mesh, axis, arity)``: dispatch-path callers get
    the same wrapper (and jit cache) back instead of re-tracing a fresh
    collective graph per window."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    n_dev = mesh.shape[axis]
    perm = ring_perm(n_dev)

    def shard_fn(*args):
        outs = fn(*args)
        outs = (outs,) if not isinstance(outs, tuple) else outs
        acc = jnp.sum(outs[tally_out])
        piece = acc

        def hop(_, carry):
            acc, piece = carry
            piece = jax.lax.ppermute(piece, axis, perm)
            return acc + piece, piece

        acc, _ = jax.lax.fori_loop(0, n_dev - 1, hop, (acc, piece))
        return (*outs, acc)

    import jax as _jax
    return _jax.jit(_shard_map_unchecked(
        shard_fn, mesh, tuple([PS(axis)] * n_in),
        tuple([PS(axis)] * n_out + [PS()])))


@functools.lru_cache(maxsize=None)
def all_to_all_resplit(fn, mesh, axis: str = "dp", *, n_in: int,
                       feature_axis: int = 1):
    """The Ulysses-style layout swap: inputs arrive ROW-sharded, an
    ``all_to_all`` re-splits them FEATURE-sharded (every device sees all
    rows for its feature slice), ``fn`` runs on the feature shard, and a
    second ``all_to_all`` restores row sharding.

    In this domain the "features" are the 65 signature bytes / 16 limbs
    of a row; the layout matters when a stage's reduction runs across
    rows (e.g. a cross-row histogram or a bytewise transform) rather
    than within them.  The pattern is the all-to-all half of the
    sequence-parallel toolbox (ring collectives being the other), kept
    here as a first-class, tested layout the verifier pipeline can adopt
    per-stage (ref role: the reference has no SP — SURVEY §5 maps the
    axis to the signature batch).

    ``fn`` maps ``n_in`` arrays of shape ``[rows, F/n]`` to one array of
    the same leading shape; the wrapper returns the row-sharded result.
    The mesh size must divide both the row count and the feature dim.
    """
    import jax
    from jax.sharding import PartitionSpec as PS

    def shard_fn(*args):
        # [rows/n, F] per device -> all_to_all -> [rows, F/n]
        resplit = [
            jax.lax.all_to_all(a, axis, split_axis=feature_axis,
                               concat_axis=0, tiled=True)
            for a in args
        ]
        out = fn(*resplit)
        # back: [rows, F/n] -> [rows/n, F]
        return jax.lax.all_to_all(out, axis, split_axis=0,
                                  concat_axis=feature_axis, tiled=True)

    from eges_tpu.parallel import shard_map_fn
    return jax.jit(shard_map_fn()(
        shard_fn, mesh=mesh, in_specs=tuple([PS(axis)] * n_in),
        out_specs=PS(axis)))


@functools.lru_cache(maxsize=None)
def ring_gather(fn, mesh, axis: str = "dp", *, n_in: int,
                gather_out: int = 0):
    """Row-sharded map whose ``gather_out`` output is ring-all-gathered:
    after N-1 neighbor hops every device holds ALL rows of that output
    (each hop forwards the chunk received last — the classic ring
    all-gather schedule).  Returns the gathered array unsharded."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    n_dev = mesh.shape[axis]
    perm = ring_perm(n_dev)

    def shard_fn(*args):
        outs = fn(*args)
        outs = (outs,) if not isinstance(outs, tuple) else outs
        local = outs[gather_out]  # [rows/n, ...]
        idx = jax.lax.axis_index(axis)
        chunks = jnp.zeros((n_dev, *local.shape), local.dtype)
        chunks = chunks.at[idx].set(local)
        moving = local

        def hop(k, carry):
            chunks, moving = carry
            moving = jax.lax.ppermute(moving, axis, perm)
            src = (idx - k - 1) % n_dev  # whose chunk just arrived
            chunks = jax.lax.dynamic_update_index_in_dim(
                chunks, moving, src, axis=0)
            return chunks, moving

        chunks, _ = jax.lax.fori_loop(0, n_dev - 1, hop, (chunks, moving))
        return chunks.reshape((-1, *local.shape[1:]))

    # every device computes the full gathered array -> replicated
    import jax as _jax
    return _jax.jit(_shard_map_unchecked(
        shard_fn, mesh, tuple([PS(axis)] * n_in), PS()))
