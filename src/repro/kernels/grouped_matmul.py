"""Grouped matrix multiply for the routed experts of a mixture of experts.

``lhs: (M, K)`` holds token rows sorted by expert, ``rhs: (G, K, N)`` one
weight matrix per expert, and ``group_sizes: (G,)`` how many consecutive rows
belong to each expert; row ``i`` of the result is its own row times its
expert's matrix. The work is proportional to the rows routed, never to a
capacity.

Two implementations, picked by the platform the program is lowered for:

* ``gmm``: the TPU Pallas kernel ``jax.experimental.pallas.ops.tpu.megablox
  .gmm`` (its custom call is named ``gmm`` in a device trace). Rows are
  padded to the kernel's row tile; the padding rows join the last group and
  are sliced off.
* ``ragged``: ``jax.lax.ragged_dot``, which XLA lowers on every backend (the
  CPU path, and the reference the kernel is tested against).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "GMM_TILE_M"]

#: Row tile of the TPU kernel: rows are padded to a multiple of it.
GMM_TILE_M = 128


def _tiling(k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn): 128 rows, and K and N tiles of up to 512 columns, so an
    expert's weights stream through VMEM in few grid steps."""
    def tile(x):
        return min(512, -(-x // 128) * 128)
    return GMM_TILE_M, tile(k), tile(n)


def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def _gmm(lhs, rhs, group_sizes, *, interpret: bool = False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    pad = (-m) % GMM_TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    out = gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(k, rhs.shape[2]),
              None, None, False, interpret)
    return out[:m]


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``(M, K)`` rows sorted by group, ``(G, K, N)`` weights, ``(G,)``
    int32 group sizes summing to ``M`` -> ``(M, N)`` in ``lhs``'s dtype
    (float32 accumulation): the kernel where the program is lowered for a
    TPU and ``ragged_dot`` elsewhere (decided at lowering, so a program
    compiled ahead of time for a TPU gets the kernel)."""
    return jax.lax.platform_dependent(lhs, rhs, group_sizes.astype(jnp.int32),
                                      tpu=_gmm, default=_ragged)
