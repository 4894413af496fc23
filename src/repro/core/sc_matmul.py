"""SC-GEMM: matrix multiplication with the paper's stochastic multiplier as the
scalar-product numeric.

Each scalar product inside the GEMM is
``a·b ≈ s_a s_b · (O(x, y) / N) · (N² Δ_a Δ_b)`` where ``O`` is the proposed
multiplier's closed form (see ``multipliers.proposed_closed_form``) and
``x, y`` are B-bit magnitudes. Accumulation across K is exact integer addition
(SC affects multiplication only — the paper targets the multiplier inside GEMM
circuits; accumulators in uGEMM-style arrays are conventional counters).

Three implementations, all bit-identical:

* :func:`sc_matmul_reference` — K-blocked broadcast, pure jnp. The oracle.
* :func:`sc_matmul_mxu_split` — the TPU-native reformulation. ``O`` splits as

      O(x, y) = msb_y · ⌊x/2⌋ + clamp(min(y_low, ⌊(x − msb_y)/2⌋), 0)

  The first term is a *true matmul* ``(s_x·⌊x/2⌋) @ (s_y·msb_y)`` and runs on
  the MXU; only the clamped-min residual needs per-pair (VPU) work. Exactness
  in fp32: magnitudes < 2¹⁵ and products < 2²⁴ for any realistic K.
* ``kernels.sc_matmul`` — the Pallas TPU kernel using the same split with
  VMEM tiling (see ``src/repro/kernels/``). Its block configuration
  (bm, bn, bk, chunk) is swept per problem shape by ``kernels.autotune``
  and reachable here through ``sc_matmul(..., impl="pallas_tuned")`` or
  ``impl="auto"``.
"""
from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp

from .sc_numerics import quantize_sign_magnitude
from .tcu import stream_length

__all__ = [
    "sc_matmul_reference",
    "sc_matmul_mxu_split",
    "sc_matmul",
    "sc_residual_term",
    "resolve_impl",
    "SC_IMPLS",
    "IMPL_ENV",
]

#: Accepted ``impl`` names ("ref" and "reference" are synonyms).
SC_IMPLS = ("auto", "ref", "reference", "mxu_split", "pallas", "pallas_tuned")

#: Environment override consulted by :func:`resolve_impl` when the config
#: leaves the choice open (``"auto"``/None).
IMPL_ENV = "REPRO_SC_IMPL"

#: Notes of the impl each :func:`sc_matmul` problem shape resolved to: INFO
#: records with args ``(site, impl)``. Under ``jax.jit`` they are written at
#: trace time, so they name what the compiled program runs.
_dispatch_log = logging.getLogger("repro.dispatch")


def _signed_counts_block(sx, mx, sy, my, bits: int) -> jax.Array:
    """Signed popcounts Σ_k s·O(x,y) for one K-block via broadcasting.

    ``mx, sx: (M, Kb)``; ``my, sy: (Kb, Nn)`` -> ``(M, Nn)`` int32.
    """
    half = stream_length(bits) // 2
    x = mx[:, :, None].astype(jnp.int32)          # (M, Kb, 1)
    y = my[None, :, :].astype(jnp.int32)          # (1, Kb, Nn)
    msb = (y >= half).astype(jnp.int32)
    y_low = y - msb * half
    o = msb * (x // 2) + jnp.maximum(jnp.minimum(y_low, (x - msb) // 2), 0)
    s = sx[:, :, None].astype(jnp.int32) * sy[None, :, :].astype(jnp.int32)
    return (s * o).sum(axis=1, dtype=jnp.int32)


def _quantize_lhs(a: jax.Array, bits: int, row_quant: bool):
    """LHS quantization: per-tensor scale, or per-row (``axis=-1``) when
    ``row_quant`` — each output row then depends only on its own input row,
    which makes batched inference *batch-composition invariant*: a sequence
    decoded in a serving slot pool alongside arbitrary neighbours produces
    the exact counts it would produce alone (DESIGN.md §7). Weights stay
    per-tensor; their scale is batch-independent already."""
    return quantize_sign_magnitude(a, bits=bits,
                                   axis=-1 if row_quant else None)


@functools.partial(jax.jit, static_argnames=("bits", "k_block", "row_quant"))
def sc_matmul_reference(a: jax.Array, b: jax.Array, *, bits: int = 8,
                        k_block: int = 128,
                        row_quant: bool = False) -> jax.Array:
    """Oracle SC-GEMM: quantize, multiply every pair via the closed form, sum.

    K is processed in blocks of ``k_block`` to bound the (M, Kb, N) broadcast.
    """
    qa = _quantize_lhs(a, bits, row_quant)
    qb = quantize_sign_magnitude(b, bits=bits)
    m, k = a.shape
    _, n = b.shape
    pad = (-k) % k_block
    if pad:
        def padk(arr, axis):
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, pad)
            return jnp.pad(arr, widths)
        sx, mx = padk(qa.sign, 1), padk(qa.mag, 1)
        sy, my = padk(qb.sign, 0), padk(qb.mag, 0)
    else:
        sx, mx, sy, my = qa.sign, qa.mag, qb.sign, qb.mag
    kp = k + pad

    def body(carry, kb):
        xs = jax.lax.dynamic_slice_in_dim(mx, kb * k_block, k_block, axis=1)
        ss = jax.lax.dynamic_slice_in_dim(sx, kb * k_block, k_block, axis=1)
        ys = jax.lax.dynamic_slice_in_dim(my, kb * k_block, k_block, axis=0)
        ts = jax.lax.dynamic_slice_in_dim(sy, kb * k_block, k_block, axis=0)
        return carry + _signed_counts_block(ss, xs, ts, ys, bits), None

    counts, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.int32),
                             jnp.arange(kp // k_block))
    nn = stream_length(bits)
    return counts.astype(jnp.float32) * (nn * qa.scale * qb.scale)


def sc_residual_term(sx, mx, sy, my, bits: int, chunk: int = 16) -> jax.Array:
    """Σ_k s_x s_y · clamp(min(y_low, ⌊(x − msb)/2⌋), 0) — the VPU residual.

    K is walked in lane-parallel chunks of ``chunk``: each scan step
    materializes one (M, chunk, N) broadcast and reduces it over the chunk
    axis, mirroring the Pallas kernel's chunked-residual layout (DESIGN.md
    §2.2). ``chunk`` bounds the peak temporary at M·chunk·N int32.
    """
    half = stream_length(bits) // 2
    m, k = mx.shape
    _, n = my.shape
    pad = (-k) % chunk
    if pad:
        mx = jnp.pad(mx, ((0, 0), (0, pad)))
        sx = jnp.pad(sx, ((0, 0), (0, pad)), constant_values=1)
        my = jnp.pad(my, ((0, pad), (0, 0)))
        sy = jnp.pad(sy, ((0, pad), (0, 0)), constant_values=1)
    kp = k + pad

    def body(carry, kb):
        x = jax.lax.dynamic_slice_in_dim(mx, kb * chunk, chunk, 1)[:, :, None].astype(jnp.int32)
        ssx = jax.lax.dynamic_slice_in_dim(sx, kb * chunk, chunk, 1)[:, :, None].astype(jnp.int32)
        y = jax.lax.dynamic_slice_in_dim(my, kb * chunk, chunk, 0)[None].astype(jnp.int32)
        ssy = jax.lax.dynamic_slice_in_dim(sy, kb * chunk, chunk, 0)[None].astype(jnp.int32)
        msb = (y >= half).astype(jnp.int32)
        y_low = y - msb * half
        res = jnp.maximum(jnp.minimum(y_low, (x - msb) // 2), 0)
        return carry + (ssx * ssy * res).sum(axis=1, dtype=jnp.int32), None

    out, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.int32), jnp.arange(kp // chunk))
    return out


@functools.partial(jax.jit, static_argnames=("bits", "chunk", "row_quant"))
def sc_matmul_mxu_split(a: jax.Array, b: jax.Array, *, bits: int = 8,
                        chunk: int = 16, row_quant: bool = False) -> jax.Array:
    """TPU-native SC-GEMM: MXU matmul term + VPU clamped-min residual.

    Bit-identical to :func:`sc_matmul_reference` (tests assert exact equality
    of the integer counts) for every ``chunk``, which only retiles the
    residual accumulation.
    """
    half = stream_length(bits) // 2
    qa = _quantize_lhs(a, bits, row_quant)
    qb = quantize_sign_magnitude(b, bits=bits)

    msb = (qb.mag >= half).astype(jnp.int32)
    # --- MXU term: (s_x · ⌊x/2⌋) @ (s_y · msb). Exact in fp32 for K < ~2^17.
    lhs = (qa.sign.astype(jnp.int32) * (qa.mag // 2)).astype(jnp.float32)
    rhs = (qb.sign.astype(jnp.int32) * msb).astype(jnp.float32)
    term1 = jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)
    # --- VPU residual.
    term2 = sc_residual_term(qa.sign, qa.mag, qb.sign, qb.mag, bits, chunk)
    counts = term1 + term2.astype(jnp.float32)
    nn = stream_length(bits)
    return counts * (nn * qa.scale * qb.scale)


def resolve_impl(impl: str | None = None) -> str:
    """Resolve an SC-GEMM implementation request (DESIGN.md §6).

    Resolution order: an explicit config value wins; ``"auto"``/None defers
    to the ``$REPRO_SC_IMPL`` environment override; absent both, the result
    stays ``"auto"`` and :func:`sc_matmul` consults the backend/autotune
    cache per shape. Unknown names fail loudly here, not deep in a trace.
    """
    if impl is None:
        impl = "auto"
    if impl not in SC_IMPLS:
        raise ValueError(
            f"unknown SC impl {impl!r}; expected one of {SC_IMPLS}")
    if impl != "auto":
        return impl
    env = os.environ.get(IMPL_ENV)
    if env:
        if env not in SC_IMPLS:
            raise ValueError(
                f"${IMPL_ENV}={env!r} is not a valid SC impl; "
                f"expected one of {SC_IMPLS}")
        return env
    return "auto"


def sc_matmul(a: jax.Array, b: jax.Array, *, bits: int = 8,
              impl: str = "mxu_split", row_quant: bool = False) -> jax.Array:
    """Dispatching entry point.

    ``impl`` ∈ {"ref"/"reference", "mxu_split", "pallas", "pallas_tuned",
    "auto"}. "pallas_tuned" runs the Pallas kernel with the autotuned block
    configuration for this problem shape (tuning on first use, then served
    from the on-disk cache); "auto" resolves per DESIGN.md §6 — the
    ``$REPRO_SC_IMPL`` override if set, else the backend-level choice from
    :func:`repro.kernels.autotune.choose_impl`. All impls are count-identical.

    ``row_quant`` quantizes the LHS with per-row scales (see
    :func:`_quantize_lhs`); the model path (``sc_layers.sc_dense``) always
    sets it so inference is batch-composition invariant.
    """
    impl = resolve_impl(impl)
    m, k = a.shape
    _, n = b.shape
    if impl == "auto":
        from repro.kernels.autotune import choose_impl
        impl = choose_impl(m, k, n, bits=bits)
    _dispatch_log.info("%s: %s", f"sc_matmul {m}x{k}x{n} b{bits}", impl)
    if impl in ("ref", "reference"):
        return sc_matmul_reference(a, b, bits=bits, row_quant=row_quant)
    if impl == "mxu_split":
        return sc_matmul_mxu_split(a, b, bits=bits, row_quant=row_quant)
    if impl == "pallas":
        from repro.kernels.ops import sc_matmul_pallas
        return sc_matmul_pallas(a, b, bits=bits, row_quant=row_quant)
    if impl == "pallas_tuned":
        from repro.kernels.ops import sc_matmul_pallas
        return sc_matmul_pallas(a, b, bits=bits, tune=True,
                                row_quant=row_quant)
    raise ValueError(f"unknown impl {impl!r}")
