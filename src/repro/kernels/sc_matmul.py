"""Pallas TPU kernel: SC-GEMM with the MXU/VPU split.

The paper's multiplier inside a GEMM decomposes per DESIGN.md §2.1 as

    O(x, y) = msb_y · ⌊x/2⌋  +  clamp(min(y_low, ⌊(x − msb_y)/2⌋), 0)
    Σ_k s_x s_y O  =  (s_x·⌊x/2⌋) @ (s_y·msb_y)   ← MXU matmul term
                    + Σ_k s_x s_y · residual(x, y)  ← VPU elementwise term

Tiling: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) so the
fp32 accumulator lives in a VMEM scratch tile across K steps.

Layout: the LHS planes enter the kernel transposed, ``(K, M)``, so K sits on
the sublane axis of *both* operands. The residual walks K in chunks of
``chunk`` rows, and a dynamic chunk offset is then a sublane offset, which
Mosaic loads straight from the VMEM ref (``pl.ds``). A lane offset that is
not a multiple of 128 — what slicing a ``(bm, bk)`` LHS tile at ``k0`` needs
— it refuses, and it has no lowering at all for a value-level
``dynamic_slice``. Each chunk's ``(chunk, bm)`` LHS rows are transposed to
``(bm, chunk)`` columns in-kernel; then every k is one ``(bm, bn)`` pass of
int32 VPU ops: an LHS column broadcast along lanes against an RHS row
broadcast along sublanes. ``M`` and the ``bm`` block lie on lanes, so a
skinny (decode) ``bm < 128`` block must span the whole padded ``M`` — which
the ops.py wrapper guarantees by padding M to a multiple of ``bm``.

VMEM working set: :meth:`repro.kernels.autotune.KernelConfig.vmem_bytes`,
which the autotuner prunes candidates against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sc_matmul_counts_pallas"]


def _kernel(bits: int, chunk: int, nsteps: int,
            sxt_ref, mxt_ref, sy_ref, my_ref, out_ref, acc_ref):
    """One (bm, bn) output tile; K accumulated across grid steps via scratch.

    ``sxt_ref, mxt_ref: (bk, bm)`` — the LHS planes, transposed;
    ``sy_ref, my_ref: (bk, bn)``. All int32.
    """
    # int32 scalars throughout: a Python int would lower as i64 under x64
    i32 = jnp.int32
    half, one = i32((1 << bits) // 2), i32(1)
    bk = mxt_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- MXU term: (s_x · ⌊x/2⌋)ᵀ-contracted with (s_y · msb). Exact in fp32
    # (|⌊x/2⌋| < 2^(bits-1), counts < 2^24); HIGHEST keeps it exact for any
    # operand width rather than leaning on bf16 holding 8-bit integers.
    msb = (my_ref[...] >= half).astype(i32)
    lhs = (sxt_ref[...] * (mxt_ref[...] >> one)).astype(jnp.float32)
    rhs = (sy_ref[...] * msb).astype(jnp.float32)
    acc = jax.lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

    # ---- VPU residual, exact in int32 (bk · 2^(bits-1) « 2^31): per chunk,
    # ``chunk`` rank-1 passes over the (bm, bn) tile. ``>> 1`` is the
    # floor-halving of the closed form (arithmetic shift on int32).
    def body(ci, res):
        rows = pl.ds(pl.multiple_of(ci * i32(chunk), chunk), chunk)
        x_c = mxt_ref[rows, :].T                  # (bm, chunk)
        sx_c = sxt_ref[rows, :].T
        y_c = my_ref[rows, :]                     # (chunk, bn)
        sy_c = sy_ref[rows, :]
        m_c = (y_c >= half).astype(i32)
        yl_c = y_c - m_c * half
        for j in range(chunk):
            r = jnp.maximum(jnp.minimum(
                yl_c[j:j + 1], (x_c[:, j:j + 1] - m_c[j:j + 1]) >> one),
                i32(0))
            res = res + (sx_c[:, j:j + 1] * sy_c[j:j + 1]) * r
        return res

    res = jax.lax.fori_loop(i32(0), i32(bk // chunk), body,
                            jnp.zeros(acc_ref.shape, i32))
    acc_ref[...] += acc + res.astype(jnp.float32)

    @pl.when(pl.program_id(2) == nsteps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bits", "bm", "bn", "bk", "chunk",
                                    "interpret"))
def sc_matmul_counts_pallas(sx, mx, sy, my, *, bits: int = 8,
                            bm: int = 128, bn: int = 128, bk: int = 512,
                            chunk: int = 8,
                            interpret: bool = False) -> jax.Array:
    """Signed SC-GEMM counts (float32 (M, N), exact integers) via Pallas.

    Inputs must be pre-padded to multiples of the block sizes (ops.py does
    this): ``sx, mx: (M, K)``; ``sy, my: (K, N)``; any integer dtype.
    ``chunk`` is the residual's k-chunk height and must divide ``bk``.
    """
    m, k = mx.shape
    k2, n = my.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"unpadded shapes ({m},{k})x({k2},{n}) for blocks ({bm},{bn},{bk})")
    assert 0 < chunk <= bk and bk % chunk == 0, (
        f"residual chunk {chunk} must divide the K block {bk}")
    assert bm % 128 == 0 or bm == m, (
        f"bm={bm} lies on lanes: below 128 it must span all of M={m}")
    nsteps = k // bk
    i32 = jnp.int32

    return pl.pallas_call(
        functools.partial(_kernel, bits, chunk, nsteps),
        grid=(m // bm, n // bn, nsteps),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, s: (s, i)),   # sxᵀ
            pl.BlockSpec((bk, bm), lambda i, j, s: (s, i)),   # mxᵀ
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # sy
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # my
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(sx.astype(i32).T, mx.astype(i32).T, sy.astype(i32), my.astype(i32))
