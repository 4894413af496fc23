"""Pallas TPU kernel: fused causal flash attention (forward).

The dry-run's roofline table shows the baseline XLA flash-as-scan materializes
O(S·block) f32 score chains to HBM (~tens of GB per layer at 4k-32k
sequences) — this kernel is the production TPU path that keeps the whole
online-softmax state in VMEM: HBM traffic collapses to Q+K+V+O read/written
once (EXPERIMENTS.md §Perf quantifies the delta).

Layout: q (B, H, Sq, D); k, v (B, KV, Skv, D) — GQA resolved in the index
map (head h reads KV head h // (H // KV)). Grid (B, H, nq, nk) with the KV
dimension innermost ("arbitrary") carrying (m, l, acc) scratch across steps.
Causal blocks strictly above the diagonal are skipped with ``pl.when``.
MXU-aligned: D and the block sizes are multiples of 128 (caller pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.errors import ConfigError

from .sc_attention import sc_pv, sc_scores

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(bq: int, bk: int, scale: float, causal: bool, nk: int,
            sc_bits, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        # kv block strictly above the diagonal -> nothing to do
        should_run = ki * bk <= qi * bq + (bq - 1)

    @pl.when(should_run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0]                              # (bk, d)
        if sc_bits is None:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        else:
            # SC score path (DESIGN.md §13): popcount contraction over the
            # quantized sign-magnitude planes, dequantized into the same
            # f32 online-softmax state the float path feeds.
            s = sc_scores(q, k, bits=sc_bits) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 128) broadcast lanes
        m_cur = jnp.max(s, axis=1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])          # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])                          # (bq, bk)
        l_new = l_ref[...][:, :1] * alpha + p.sum(axis=1, keepdims=True)
        if sc_bits is None:
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            pv = sc_pv(p, v[None].astype(jnp.float32), bits=sc_bits)  # (bq, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret",
                                             "sc_bits"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 256, bk: int = 512,
                           interpret: bool = False,
                           sc_bits: int | None = None) -> jax.Array:
    """``q: (B, H, Sq, D)``; ``k, v: (B, KV, Skv, D)``; returns ``(B, H, Sq, D)``.

    Sq/Skv must be multiples of bq/bk and D of 128 (ops-level callers pad).
    ``sc_bits`` switches the QK^T/PV contractions to the SC popcount path
    (DESIGN.md §13); ``None`` is the exact float path.
    """
    b, h, sq, d = q.shape
    _, kv, skv, _ = k.shape
    g = h // kv
    if sq % bq or skv % bk:
        # The grid below floors sq//bq, skv//bk — a non-multiple shape would
        # silently leave the tail rows as uninitialized garbage.
        raise ConfigError(
            f"flash kernel needs Sq % bq == 0 and Skv % bk == 0 (callers "
            f"pad): got Sq={sq}, Skv={skv} with bq={bq}, bk={bk}")
    nq, nk = sq // bq, skv // bk
    scale = d ** -0.5

    kernel = functools.partial(_kernel, bq, bk, scale, causal, nk, sc_bits)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
