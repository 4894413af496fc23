"""GPipe-style pipeline parallelism utility.

Default configs use DP+TP+EP+SP (better fit for v5e pods — DESIGN.md §3), but
PP is required equipment at 1000+ nodes when a model's layers outgrow one
pod's TP reach. This module provides a self-contained, shard_map-based
schedule: stages hold contiguous layer slices, microbatches stream through
``jax.lax.ppermute`` transfers, and the bubble is the standard (S-1)/(M+S-1).

The implementation is deliberately generic: ``stage_fn(stage_params, x)`` is
any per-stage function; tests drive it with an MLP stack and assert
bit-equality with the unpipelined forward.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward"]

def pipeline_forward(stage_fn: Callable, stage_params, x: jax.Array, *,
                     mesh: Mesh, axis: str = "stage",
                     n_microbatches: int) -> jax.Array:
    """Run ``x`` through S pipeline stages laid out on mesh axis ``axis``.

    ``stage_params``: pytree whose leaves have leading dim S (one slice per
    stage). ``x: (B, ...)`` with ``B % n_microbatches == 0``. Returns the
    final-stage output for the full batch.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0
    mb = b // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    def per_stage(params, micro_local):
        stage_id = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)   # this stage's slice
        n_ticks = n_microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(state, t):
            buf, outputs = state
            # stage 0 injects microbatch t (or zeros once drained)
            inject = jnp.where(t < n_microbatches,
                               micro_local[jnp.minimum(t, n_microbatches - 1)],
                               jnp.zeros_like(buf))
            x_in = jnp.where(stage_id == 0, inject, buf)
            y = stage_fn(params, x_in)
            # last stage records its result at slot t - (n_stages - 1)
            slot = t - (n_stages - 1)
            outputs = jax.lax.cond(
                (stage_id == n_stages - 1) & (slot >= 0),
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(slot, 0),) + (0,) * y.ndim),
                lambda o: o, outputs)
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, outputs), None

        init_buf = jnp.zeros_like(micro_local[0])
        init_out = jnp.zeros((n_microbatches, *micro_local.shape[1:]),
                             micro_local.dtype)
        (buf, outputs), _ = jax.lax.scan(tick, (init_buf, init_out),
                                         jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all (psum of one-hot)
        is_last = (stage_id == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * is_last, axis)
        return outputs

    shard = functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)

    outputs = shard(per_stage)(stage_params, micro)
    return outputs.reshape(b, *x.shape[1:])
