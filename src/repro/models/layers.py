"""Shared model layers: RMSNorm, RoPE / M-RoPE, GQA attention.

Attention is a pure-JAX "flash" formulation — ``lax.map`` over query blocks
with an inner ``lax.scan`` over key/value blocks and an online-softmax
accumulator — so activations stay O(block²) instead of O(S²) and the same
code lowers for 4k training, 32k prefill and (with a KV cache) decode. GQA is
computed with grouped einsums (no KV head materialization/repeat). Features
required by the assigned architectures are flags: sliding windows (gemma2
local layers, llama4 chunked), logit softcap (gemma2), QK-norm (qwen3),
M-RoPE (qwen2-vl), QKV bias (qwen2).
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.sc_attention import sc_attention_bits_ok, sc_pv, sc_scores

__all__ = ["rms_norm", "rope", "apply_rope", "apply_mrope", "flash_attention",
           "decode_attention", "paged_decode_attention", "PagedKV", "softcap"]


def rms_norm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    """RMSNorm in fp32 with bf16-safe cast back. ``plus_one`` is gemma-style (1+w)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    out = x * (1.0 + w if plus_one else w)
    return out.astype(dtype)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for positions ``(..., S)`` -> ``(..., S, head_dim/2)``."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x: (B, S, H, D)`` with tables ``(B, S, D/2)`` (half-split convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, sections: tuple[int, ...],
                theta: float) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): ``positions (3, B, S)`` are (t, h, w) ids.

    The rotary half-dim is partitioned into ``sections`` (e.g. 16/24/24 for
    head_dim 128); each section rotates by its own positional stream.
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (3, B, S, half)
    parts = []
    start = 0
    for axis, sec in enumerate(sections):
        parts.append(angles[axis, :, :, start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)                      # (B, S, half)
    return apply_rope(x, jnp.cos(ang), jnp.sin(ang))


#: Trace-time notes of the path each attention call site took: INFO records
#: with args ``(site, "<path>: <why>")``. Written when a step is traced, so
#: they describe what the compiled programs run; ``chip_smoke.py`` prints
#: them.
_dispatch_log = logging.getLogger("repro.dispatch")


class _FlashCarry(NamedTuple):
    m: jax.Array      # running max      (B, KV, G, Q)
    l: jax.Array      # running sum      (B, KV, G, Q)
    o: jax.Array      # running output   (B, KV, G, Q, D)


def _flash_kernel_eligible(sq: int, skv: int, d: int, *, causal: bool,
                           window: int | None,
                           logit_softcap: float | None,
                           bf16_probs: bool,
                           sc_bits: int | None = None) -> bool:
    """Shapes/features the fused Pallas flash kernel can serve: plain causal
    self-attention on MXU-aligned extents. ``bf16_probs`` disqualifies — the
    kernel keeps fp32 probs, and silently mixing prob precisions across a
    model's layers would change training numerics. The SC score path shares
    the float envelope (its contraction swaps; the masking/softmax shell is
    the same) but requires a supported operand width."""
    return (causal and window is None and logit_softcap is None
            and not bf16_probs and sc_attention_bits_ok(sc_bits)
            and sq == skv and sq % 128 == 0 and d % 128 == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_kernel_call(q: jax.Array, k: jax.Array, v: jax.Array,
                       q_block: int, kv_block: int,
                       skip_masked_blocks: bool,
                       sc_bits: int | None = None) -> jax.Array:
    """Tuned Pallas flash forward in layer layout (B, S, H, D).

    The kernel is forward-only (no backward Mosaic kernel yet), so gradients
    recompute through the jnp online-softmax formulation below — the same
    math, so this is a true VJP, not an STE. ``q_block/kv_block`` and
    ``skip_masked_blocks`` configure that recompute (the triangular-skip
    schedule matters in the backward too). For ``sc_bits`` the recompute
    routes through the jnp SC branch; the quantization steps are
    round/clip, so the VJP is piecewise-constant like any quantized path.
    """
    from repro.kernels.ops import flash_attention_tuned
    out = flash_attention_tuned(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), causal=True,
                                sc_bits=sc_bits)
    return out.transpose(0, 2, 1, 3)


def _flash_kernel_call_fwd(q, k, v, q_block, kv_block, skip_masked_blocks,
                           sc_bits):
    return (_flash_kernel_call(q, k, v, q_block, kv_block,
                               skip_masked_blocks, sc_bits), (q, k, v))


def _flash_kernel_call_bwd(q_block, kv_block, skip_masked_blocks, sc_bits,
                           res, g):
    q, k, v = res
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def ref(q, k, v):
        return flash_attention(q, k, v, q_positions=pos, kv_positions=pos,
                               causal=True, q_block=q_block,
                               kv_block=kv_block,
                               skip_masked_blocks=skip_masked_blocks,
                               kernel_impl="jnp", sc_bits=sc_bits)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


_flash_kernel_call.defvjp(_flash_kernel_call_fwd, _flash_kernel_call_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_positions: jax.Array, kv_positions: jax.Array,
                    causal: bool = True, window: int | None = None,
                    logit_softcap: float | None = None,
                    q_block: int = 512, kv_block: int = 1024,
                    skip_masked_blocks: bool = False,
                    bf16_probs: bool = False,
                    kernel_impl: str = "auto",
                    canonical_positions: bool = False,
                    sc_bits: int | None = None) -> jax.Array:
    """Blocked online-softmax attention with grouped (GQA) einsums.

    ``q: (B, Sq, H, D)``; ``k, v: (B, Skv, KV, D)`` with ``H % KV == 0``.
    ``*_positions: (B, Sq)/(B, Skv)`` absolute positions used for the causal /
    sliding-window mask.

    ``skip_masked_blocks=True`` switches the inner loop to a dynamic upper
    bound derived from the causal structure — the §Perf optimization that
    removes the ~2x full-sweep FLOP waste for causal training shapes (valid
    for the canonical 0..S-1 position layout).

    ``kernel_impl`` dispatches the fused Pallas kernel (DESIGN.md §6):
    "auto" uses it on TPU when the shape/features qualify (plain causal
    self-attention, 128-aligned S and D, fp32 probs); "pallas_tuned" uses it
    on every eligible call regardless of backend (interpret mode off TPU —
    used by tests) and falls back to jnp on ineligible ones (windows,
    softcap, ragged extents); "jnp" forces the XLA formulation below. The
    kernel's (bq, bk) blocks resolve through the autotune cache.

    The kernel masks with a built-in 0..S-1 causal mask and never reads
    ``q_positions``/``kv_positions``, so it only engages when the caller
    declares ``canonical_positions=True`` — with the default False, packed /
    restarted position layouts always take the position-aware jnp path.

    ``sc_bits`` routes the QK^T and PV contractions through the SC popcount
    path (DESIGN.md §13) in both the kernel and the jnp formulation; per-row
    quantization keeps batched SC attention bit-identical to sequential.
    """
    b, sq, h, d = q.shape
    _, skv, kv_heads, _ = k.shape

    if kernel_impl not in ("auto", "jnp", "pallas_tuned"):
        raise ValueError(f"unknown attention kernel_impl {kernel_impl!r}")
    if sc_bits is not None:
        # the SC PV is already a quantized contraction with an f32 running
        # state; a second bf16 squeeze on probs would change the quantizer's
        # inputs for no traffic win (probs never hit HBM on the SC path)
        bf16_probs = False
    eligible = canonical_positions and _flash_kernel_eligible(
        sq, skv, d, causal=causal, window=window,
        logit_softcap=logit_softcap, bf16_probs=bf16_probs, sc_bits=sc_bits)
    backend = jax.default_backend()
    use_kernel = (kernel_impl == "pallas_tuned" and eligible) or (
        kernel_impl == "auto" and eligible and backend == "tpu")
    if kernel_impl == "jnp":
        why = "kernel_impl='jnp'"
    elif not canonical_positions:
        why = "explicit positions (the kernel masks 0..S-1 only)"
    elif not eligible:
        why = (f"outside the kernel envelope (causal={causal}, "
               f"window={window}, softcap={logit_softcap}, "
               f"bf16_probs={bf16_probs}, S={sq}/{skv}, d={d}; needs "
               f"causal self-attention with S, d multiples of 128)")
    elif not use_kernel:
        why = f"kernel_impl='auto' on backend {backend!r}"
    else:
        why = f"kernel_impl={kernel_impl!r}, eligible"
    _dispatch_log.info(
        "%s: %s", f"flash sc{sc_bits or 0} B{b} S{sq}/{skv} H{h}/{kv_heads} "
        f"d{d}", f"{'pallas' if use_kernel else 'jnp'}: {why}")
    if use_kernel:
        return _flash_kernel_call(q, k, v, q_block, kv_block,
                                  skip_masked_blocks, sc_bits)
    g = h // kv_heads
    scale = d ** -0.5

    pq = (-sq) % q_block
    pk = (-skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=jnp.iinfo(jnp.int32).max)
    nq, nk = (sq + pq) // q_block, (skv + pk) // kv_block

    # (nq, B, qb, KV, G, D) query blocks in grouped layout
    q_blocks = q.reshape(b, nq, q_block, kv_heads, g, d).transpose(1, 0, 2, 3, 4, 5)
    qpos_blocks = q_positions.reshape(b, nq, q_block).transpose(1, 0, 2)
    k_blocks = k.reshape(b, nk, kv_block, kv_heads, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, nk, kv_block, kv_heads, d).transpose(1, 0, 2, 3, 4)
    kpos_blocks = kv_positions.reshape(b, nk, kv_block).transpose(1, 0, 2)

    neg = jnp.float32(-1e30)

    def make_kv_step(qb, qp):
        def kv_step(carry: _FlashCarry, ki):
            kb, vb, kp = k_blocks[ki], v_blocks[ki], kpos_blocks[ki]
            if sc_bits is not None:
                # SC QK^T (DESIGN.md §13): per-row quantized popcount
                # contraction; padded/masked rows quantize independently and
                # their masked scores underflow to exact zeros downstream.
                q_al = qb.transpose(0, 2, 3, 1, 4)          # (b, c, g, qb, d)
                k_al = kb.transpose(0, 2, 1, 3)[:, :, None]  # (b, c, 1, kb, d)
                s = sc_scores(q_al, k_al, bits=sc_bits) * scale
            else:
                s = jnp.einsum("bqcgd,bkcd->bcgqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            s = softcap(s, logit_softcap)
            mask = jnp.ones((b, q_block, kv_block), bool)
            if causal:
                mask &= qp[:, :, None] >= kp[:, None, :]
            if window is not None:
                mask &= (qp[:, :, None] - kp[:, None, :]) < window
            s = jnp.where(mask[:, None, None, :, :], s, neg)
            m_new = jnp.maximum(carry.m, s.max(axis=-1))
            alpha = jnp.exp(carry.m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = carry.l * alpha + p.sum(axis=-1)
            if bf16_probs:
                # §Perf: probs in bf16 for the PV matmul — halves the
                # score-chain HBM bytes; sums stay f32 (flash-attention
                # standard practice)
                # repro-lint: disable=R5 -- deliberate §Perf bf16 squeeze; accumulation stays f32 via preferred_element_type
                pv = jnp.einsum("bcgqk,bkcd->bcgqd", p.astype(jnp.bfloat16),
                                # repro-lint: disable=R5 -- deliberate §Perf bf16 squeeze; accumulation stays f32
                                vb.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            elif sc_bits is not None:
                # SC PV: value rows aligned (b, c, 1, 1, kb, d) against the
                # block-local unnormalized probs (b, c, g, qb, kb)
                v_al = vb.astype(jnp.float32).transpose(
                    0, 2, 1, 3)[:, :, None, None]
                pv = sc_pv(p, v_al, bits=sc_bits)            # (b, c, g, qb, d)
            else:
                pv = jnp.einsum("bcgqk,bkcd->bcgqd", p,
                                vb.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
            o_new = carry.o * alpha[..., None] + pv
            return _FlashCarry(m_new, l_new, o_new), None
        return kv_step

    def init_carry():
        return _FlashCarry(
            m=jnp.full((b, kv_heads, g, q_block), neg, jnp.float32),
            l=jnp.zeros((b, kv_heads, g, q_block), jnp.float32),
            o=jnp.zeros((b, kv_heads, g, q_block, d), jnp.float32))

    def finish(carry):
        out = carry.o / jnp.maximum(carry.l, 1e-30)[..., None]
        # (B, KV, G, Q, D) -> (B, Q, KV, G, D) -> (B, Q, H, D)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, q_block, h, d)

    if skip_masked_blocks and causal and window is None:
        # §Perf triangular schedule: q blocks unrolled (static), each scanning
        # only the kv blocks at or below its diagonal — differentiable (static
        # trip counts) and removes the ~2x full-sweep FLOP/byte waste.
        outs = []
        for qi in range(nq):
            limit = min(qi * q_block // kv_block + 1, nk)
            kv_step = make_kv_step(q_blocks[qi], qpos_blocks[qi])
            carry, _ = jax.lax.scan(kv_step, init_carry(), jnp.arange(limit))
            outs.append(finish(carry))
        out = jnp.stack(outs, axis=0)
    else:
        def q_step(qb, qp):
            kv_step = make_kv_step(qb, qp)
            carry, _ = jax.lax.scan(kv_step, init_carry(), jnp.arange(nk))
            return finish(carry)

        out = jax.lax.map(lambda args: q_step(*args), (q_blocks, qpos_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sq + pq, h, d)
    return out[:, :sq].astype(q.dtype)


class PagedKV(NamedTuple):
    """One attention site's KV state in the paged pool layout (DESIGN.md
    §8/§9), as the decode paths thread it through a layer: page pools
    ``k, v: (P, block, KV, hd)`` (last page = trash block) plus the shared
    ``(capacity, max_blocks)`` block table. Family decode steps build one
    per layer from the scanned cache leaves; ``_attn_forward`` recognizes
    it and takes the fused paged path instead of the dense-view scatter."""
    k: jax.Array
    v: jax.Array
    tables: jax.Array

    @property
    def block(self) -> int:
        return self.k.shape[1]

    @property
    def trash(self) -> int:
        return self.k.shape[0] - 1


def _gather_pages(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """One leaf's gathered-dense view: ``(P, block, KV, D)`` pages through a
    ``(C, MB)`` table -> ``(C, MB·block, KV, D)``, unallocated entries
    redirected to the trash block — ``cache_ops.paged_gather`` for a single
    lead slice, kept bit-identical to it (same redirect, same reshape)."""
    safe = jnp.where(tables < 0, pages.shape[0] - 1, tables)
    g = pages[safe]                            # (C, MB, block, KV, D)
    c, mb, blk = g.shape[:3]
    return g.reshape(c, mb * blk, *g.shape[3:])


def _paged_kernel_eligible(g: int, d: int, block: int,
                           logit_softcap: float | None,
                           interpret: bool, *, kv: int = 2,
                           max_blocks: int = 1,
                           sc_bits: int | None = None) -> bool:
    """Layouts the fused paged kernel serves *bit-identically* to the
    gathered-dense path (kernels/paged_attention.py): GQA head grouping
    (g ≥ 2, per-page score tiles) and — via the whole-row finish einsum —
    full-MHA (g == 1, which needs kvh ≥ 2 per grid step and therefore
    kv ≥ 2); no logit softcap (the tanh chain fuses differently per
    program). The tuning grid must also be non-empty — single-KV-head
    full-MHA has no kvh ≥ 2 split, and a whole-row scratch too big for
    the VMEM budget (huge ``max_blocks · block``) has no valid candidate;
    either way the dispatch must fall back to the gather rather than let
    the tuner raise mid-trace.

    The SC variant (``sc_bits``) widens the envelope: its popcount
    contraction has no einsum lowering sensitivity, so every head layout —
    including single-KV-head full-MHA — stays bit-identical and the
    candidate grid keeps ``kvh = 1``. Softcap remains out (same tanh-fusion
    drift as the float path).

    Interpret mode only. Compiled for TPU, Mosaic refuses the kernel at
    every layout: with ``kvh < KV`` the ``(1, block, kvh, d)`` block over
    the ``(P, block, KV, D)`` pool breaks the (8, 128) tiling rule; with
    ``kvh == KV`` the float path fails with "'tpu.matmul' op Not
    implemented: Up to 1 batch dim supported" (its 5-D einsums) and the SC
    path with "infer-vector-layout: unsupported shape cast" (its
    reshapes). So a compiled backend always takes the gathered path."""
    if not interpret:
        return False
    if logit_softcap is not None or not sc_attention_bits_ok(sc_bits):
        return False
    from repro.kernels.autotune import candidate_paged_configs
    return bool(candidate_paged_configs(kv, g, d, block=block,
                                        max_blocks=max_blocks,
                                        sc=sc_bits is not None))


def paged_decode_attention(q: jax.Array, paged: PagedKV, *,
                           q_position: jax.Array,
                           window: int | None = None,
                           logit_softcap: float | None = None,
                           kernel_impl: str = "auto",
                           sc_bits: int | None = None) -> jax.Array:
    """Single-step attention straight against the paged KV pool.

    ``q: (C, 1, H, D)``; ``paged`` holds this site's page pools and block
    table; ``q_position: (C,)``. :func:`_paged_kernel_eligible` holds in
    interpret mode only, and only ``kernel_impl="pallas_tuned"`` takes the
    kernel there (the bit-identity tests); "auto" and "jnp" take the
    gathered-dense formulation. Ineligible calls (compiled backends,
    softcap layers, single-KV-head full-MHA) always gather — per layer,
    never the whole cache tree.
    """
    if kernel_impl not in ("auto", "jnp", "pallas_tuned"):
        raise ValueError(f"unknown paged attention kernel_impl "
                         f"{kernel_impl!r}")
    c, _, h, d = q.shape
    kv = paged.k.shape[2]
    g = h // kv
    from repro.kernels.ops import default_interpret
    interpret = default_interpret()
    eligible = _paged_kernel_eligible(g, d, paged.block, logit_softcap,
                                      interpret, kv=kv,
                                      max_blocks=paged.tables.shape[1],
                                      sc_bits=sc_bits)
    use_kernel = kernel_impl == "pallas_tuned" and eligible
    if kernel_impl == "jnp":
        why = "kernel_impl='jnp'"
    elif not interpret:
        why = "compiled backend: the paged kernel does not lower with Mosaic"
    elif not eligible:
        why = (f"outside the bit-identity envelope (g={g}, kv={kv}, "
               f"softcap={logit_softcap}, sc_bits={sc_bits}, or no "
               f"tuning candidate fits VMEM)")
    elif not use_kernel:
        why = "kernel_impl='auto' (only 'pallas_tuned' takes the kernel)"
    else:
        why = "kernel_impl='pallas_tuned' in interpret mode"
    _dispatch_log.info(
        "%s: %s", f"paged sc{sc_bits or 0} C{c} H{h}/{kv} d{d} "
        f"blk{paged.block}x{paged.tables.shape[1]}",
        f"{'pallas' if use_kernel else 'gather'}: {why}")
    if use_kernel:
        from repro.kernels.ops import paged_decode_attention_tuned
        out = paged_decode_attention_tuned(
            q[:, 0].reshape(c, kv, g, d), paged.k, paged.v, paged.tables,
            q_position, window=window, logit_softcap=logit_softcap,
            sc_bits=sc_bits)
        return out.reshape(c, 1, h, d)
    return decode_attention(q, _gather_pages(paged.k, paged.tables),
                            _gather_pages(paged.v, paged.tables),
                            q_position=q_position, window=window,
                            logit_softcap=logit_softcap, sc_bits=sc_bits)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     q_position: jax.Array, window: int | None = None,
                     logit_softcap: float | None = None,
                     sc_bits: int | None = None) -> jax.Array:
    """Decode-window attention against a (possibly partially filled) KV cache.

    ``q: (B, W, H, D)`` — W consecutive query rows per sequence (W = 1 for
    the ordinary decode step; W = k + 1 for a speculative verify window,
    DESIGN.md §14); ``k_cache, v_cache: (B, S, KV, D)``;
    ``q_position: (B,)`` absolute position of the *first* query row (row i
    sits at ``q_position + i``). Each row masks cache slots past its own
    position (unfilled future slots, and the window's later rows), one
    exact fp32 softmax per row — never an online-softmax rescale, which is
    what keeps a W-row verify bit-comparable to W sequential single-row
    steps (DESIGN.md §9's masking contract). ``sc_bits`` switches the
    score/PV contractions to the SC popcount path; per-row quantization and
    exact-zero masked terms keep the result invariant to the cache extent
    and batch composition (DESIGN.md §13).
    """
    b, w, h, d = q.shape
    _, s, kv_heads, _ = k_cache.shape
    g = h // kv_heads
    scale = d ** -0.5
    qg = q.reshape(b, w, kv_heads, g, d)
    if sc_bits is not None:
        q_al = qg.transpose(0, 2, 3, 1, 4)               # (b, c, g, W, d)
        k_al = k_cache.transpose(0, 2, 1, 3)[:, :, None]  # (b, c, 1, S, d)
        scores = sc_scores(q_al, k_al, bits=sc_bits) * scale
    else:
        scores = jnp.einsum("bqcgd,bkcd->bcgqk", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, logit_softcap)
    kpos = jnp.arange(s)[None, None, :]                 # (1, 1, S)
    row_pos = q_position[:, None] + jnp.arange(w)[None, :]       # (B, W)
    mask = kpos <= row_pos[:, :, None]                  # (B, W, S)
    if window is not None:
        mask &= (row_pos[:, :, None] - kpos) < window
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if sc_bits is not None:
        # value rows aligned (b, c, 1, 1, S, d) against p (b, c, g, W, S) —
        # the same operand alignment the fused paged kernel's finish uses
        v_al = v_cache.astype(jnp.float32).transpose(
            0, 2, 1, 3)[:, :, None, None]
        out = sc_pv(p, v_al, bits=sc_bits)               # (b, c, g, W, d)
    else:
        out = jnp.einsum("bcgqk,bkcd->bcgqd", p, v_cache.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, w, h, d)
    return out.astype(q.dtype)
