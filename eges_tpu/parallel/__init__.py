"""Device-mesh parallelism utilities (the SPMD layer).

The "data parallelism" of this domain is sharding signature-batch ROWS
across chips (SURVEY §2.3: the reference's per-tx verify loop maps to
the batch dimension; multi-chip = `shard_map` over a 1-axis mesh with
XLA collectives riding ICI).  These helpers are the generic layer under
:func:`eges_tpu.crypto.verifier.make_sharded_ecrecover`.
"""

from __future__ import annotations

import functools

import numpy as np


def shard_map_fn():
    """``jax.shard_map`` as every sharded graph in the tree uses it:
    without the varying-mesh-axes check.  On the TPU the mapped function
    is a chain of ``pallas_call`` kernels whose ``out_shape`` structs
    carry no ``vma`` annotation, and with ``check_vma=True`` tracing
    them inside the map is refused outright (found compiling the
    four-device graph for a described v5e, PR 22 — the CPU graph path
    has no kernels and never tripped it).  The maps here are pure row
    parallelism plus one explicit ``psum``/``ppermute`` tally, so the
    check buys nothing."""
    import jax

    return functools.partial(jax.shard_map, check_vma=False)


def data_parallel_mesh(devices=None, axis: str = "dp"):
    """A 1-axis mesh over ``devices`` (default: all local devices)."""
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    return jax.sharding.Mesh(np.array(devs), (axis,))


@functools.lru_cache(maxsize=None)
def shard_rows(fn, mesh, axis: str = "dp", *, n_in: int, n_out: int,
               tally_out: int | None = None):
    """Wrap a row-batched function in `shard_map` over ``mesh[axis]``.

    Memoized on ``(fn, mesh, axis, arity)`` — the wrapper (and its jit
    cache) is built once per distinct graph, so calling this from the
    dispatch path never re-traces.

    ``fn`` maps ``n_in`` row-sharded arrays to ``n_out`` row-sharded
    arrays; each device runs the identical fused kernel on its shard
    (pure data parallel — XLA inserts no collectives for the map).
    When ``tally_out`` names an output index, that output is additionally
    `psum`-reduced over the mesh axis into an unsharded scalar appended
    to the outputs — the on-device ACK-tally reduction
    (ref: core/geec_state.go:1184-1227 handleVerifyReplies).
    """
    import jax
    from jax.sharding import PartitionSpec as PS

    def shard_fn(*args):
        outs = fn(*args)
        outs = (outs,) if not isinstance(outs, tuple) else outs
        if tally_out is not None:
            import jax.numpy as jnp

            tally = jax.lax.psum(jnp.sum(outs[tally_out]), axis)
            outs = (*outs, tally)
        return outs

    out_specs = tuple([PS(axis)] * n_out
                      + ([PS()] if tally_out is not None else []))
    return jax.jit(
        shard_map_fn()(shard_fn, mesh=mesh,
                       in_specs=tuple([PS(axis)] * n_in),
                       out_specs=out_specs))
