"""Pallas TPU kernel: fused paged-attention for decode (DESIGN.md §9).

The paged serving path (DESIGN.md §8) stores KV state as a shared pool of
``block``-token pages addressed through per-slot block tables. Before this
kernel, every decode step materialized the gathered dense per-slot view —
a ``capacity × max_blocks·block`` HBM transient per sequence leaf — just so
the dense ``decode_attention`` could consume it. This kernel walks the block
table *inside* the kernel instead: the table and the per-slot positions are
scalar-prefetched, the K/V ``BlockSpec`` index maps translate (slot, logical
page) → physical page per grid step, and the page pool is read in place.
The transient disappears; per-step working memory is the VMEM scratch below,
which scales with ``max_blocks · block`` (one sequence), never with
capacity. Same "compute where the bits live" move as the paper's
bit-parallel multiplier — restructure the storage walk, keep the arithmetic.

**Bit-identity contract.** Decode attention has exactly one query token per
slot, so the whole score row fits in VMEM. Instead of online-softmax
(whose running rescale by ``exp(m_prev - m_new)`` re-rounds the
accumulator), the kernel buffers per-page scores and fp32 V tiles in
scratch and takes ONE exact softmax at the last page — the same
``max → exp → sum → divide → PV`` reduction, over the same element order
(page-major position order = the dense S axis) and the same einsum dim
structure, as ``cache_ops.paged_gather`` + ``models.layers.decode_attention``
(the dim structure matters: XLA CPU picks its contraction micro-kernel by
shape, and a differently-shaped dot over the same elements drifts 1–2 ulp).
Pages the table leaves unallocated (entry −1) are redirected to the trash
block exactly like ``paged_gather``; positions past a slot's ``pos`` (and
outside its sliding window) mask to −1e30, whose fp32 softmax term
underflows to exactly 0.0. Fully masked pages skip their dot products and
write the −1e30 / zero tiles directly — bitwise the same result, none of
the work.

**Exactness envelope** (verified by tests/test_paged_attention.py): bitwise
equality with the gathered-dense path holds for GQA head layouts
(``H // KV ≥ 2``) and — since the whole-row variant below — full-MHA
``H == KV``, with or without sliding windows, fp32 or bf16. At ``G == 1``
XLA collapses the dense path's size-1 group dim into contraction shapes a
per-page score call cannot mimic, so that path buffers *raw* K pages in
scratch instead and runs one whole-row score einsum at the last page —
operand shapes exactly as the gathered path's per-slot slice, which is
bitwise (it also needs ``kvh ≥ 2`` per grid step: a single-head slice
lowers differently, so ``autotune.candidate_paged_configs`` never proposes
``G == 1, kvh == 1`` and this function rejects it). Two regimes remain
outside the envelope and are dispatch-ineligible in
``models.layers.paged_decode_attention`` (mirroring the flash kernel's
feature gate): logit softcap — the ``tanh`` chain fuses differently in the
two programs — and single-KV-head full-MHA (``KV == 1``), where no
``kvh ≥ 2`` split exists. Both fall back to the per-layer gather, which
still avoids the all-layer dense transient the pre-fused path materialized.

Layout: ``q (C, KV, G, D)`` — one token per slot, heads grouped per KV head
(head ``h`` of the layer layout is ``(h // G, h % G)``); ``k_pages,
v_pages (P, block, KV, D)`` with page ``P - 1`` the trash block;
``tables (C, MB) int32``; ``q_positions (C,) int32``. Grid
``(C, KV // kvh, MB)`` with the page walk innermost ("arbitrary") carrying
the scratch; ``kvh`` (KV heads per grid step) is the
:class:`repro.kernels.autotune.PagedFlashConfig` tuning knob.

Compiled-TPU alignment wants ``D % 128 == 0`` and ``block % 8 == 0``
(lane / fp32-sublane tiling); interpret mode (this container, the test
suite) has no such constraint — ``models.layers.paged_decode_attention``
gates dispatch accordingly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.errors import ConfigError

from .sc_attention import sc_pv, sc_scores

__all__ = ["paged_attention_pallas"]

NEG_INF = -1e30


def _kernel(block: int, max_blocks: int, scale: float, window: int | None,
            logit_softcap: float | None, sc_bits: int | None,
            tables_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref, sk_ref, vb_ref):
    ci = pl.program_id(0)
    ji = pl.program_id(2)
    qpos = qpos_ref[ci]
    page_start = ji * block
    g = q_ref.shape[2]
    kvh = q_ref.shape[1]
    s_len = max_blocks * block

    if g == 1 and sc_bits is None:
        # Full-MHA path: per-page score tiles are NOT in the bit-identity
        # envelope here — with a size-1 group dim XLA lowers the dense
        # path's score einsum to a contraction whose bits a block-length
        # call cannot reproduce. Instead buffer the raw K page (the trash
        # redirect in the BlockSpec index map already mirrors the gather)
        # and run ONE whole-row score einsum at the last page, which IS
        # bit-identical to the gathered-dense call (empirically: per-slot
        # b=1 whole-row calls match; per-page calls and kvh=1 slices do
        # not — hence the kvh >= 2 requirement enforced at dispatch).
        sk_ref[ji] = k_ref[0]
        vb_ref[ji] = v_ref[0].astype(jnp.float32)
    else:
        # A page whose every position masks out contributes exactly the
        # -1e30 scores / zero-weighted V rows the dense path computes for
        # it — write those tiles directly and skip both dot products.
        fully_masked = page_start > qpos
        if window is not None:
            fully_masked |= qpos - (page_start + block - 1) >= window

        @pl.when(jnp.logical_not(fully_masked))
        def _score():
            q = q_ref[...]                           # (1, kvh, g, d)
            k = k_ref[...]                           # (1, block, kvh, d)
            if sc_bits is not None:
                # SC scores are popcount contractions — elementwise integer
                # sums with no einsum lowering sensitivity, so a per-page
                # tile reproduces the gathered-dense SC bits at *any* head
                # layout (no g >= 2 / kvh >= 2 restriction; DESIGN.md §13).
                q_r = q[0][:, :, None, :]                      # (kvh, g, 1, d)
                k_r = k[0].transpose(1, 0, 2)[:, None, :, :]   # (kvh, 1, bl, d)
                s = sc_scores(q_r, k_r, bits=sc_bits)[:, :, 0, :] * scale
            else:
                # literally the dense path's score einsum — same dim
                # structure ("bqcgd,bkcd->bcgqk" with b=1, q folded into the
                # lead axis), so XLA lowers the same contraction
                # micro-kernel and the bits match
                s = jnp.einsum("bqcgd,bkcd->bcgqk", q[None], k,
                               preferred_element_type=jnp.float32) * scale
                s = s[0, :, :, 0]                    # (kvh, g, block)
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            kpos = page_start + jax.lax.broadcasted_iota(jnp.int32,
                                                         s.shape, 2)
            mask = kpos <= qpos
            if window is not None:
                mask &= (qpos - kpos) < window
            sk_ref[ji] = jnp.where(mask, s, NEG_INF)
            vb_ref[ji] = v_ref[0].astype(jnp.float32)

        @pl.when(fully_masked)
        def _skip():
            sk_ref[ji] = jnp.full_like(sk_ref[ji], NEG_INF)
            vb_ref[ji] = jnp.zeros_like(vb_ref[ji])

    @pl.when(ji == max_blocks - 1)
    def _finish():
        if g == 1 and sc_bits is None:
            # whole-row scores over the buffered pages, flattened back to
            # the dense S axis — operand shapes exactly as the gathered
            # path's b=1 slice, so the lowering (and the bits) coincide
            k = sk_ref[...].reshape(1, s_len, kvh, -1)
            s = jnp.einsum("bqcgd,bkcd->bcgqk", q_ref[...][None], k,
                           preferred_element_type=jnp.float32) * scale
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
            mask = kpos <= qpos
            if window is not None:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)          # (1, kvh, 1, 1, S)
        else:
            # Exact softmax over the full row. The reductions must run
            # over a trailing S axis in page-major position order —
            # reducing the raw (MB, kvh, g, block) scratch over (0, 3)
            # associates the sum differently and drifts 1-2 ulp off the
            # dense jax.nn.softmax. The transposes/reshapes themselves are
            # bit-exact.
            s = sk_ref[...].transpose(1, 2, 0, 3).reshape(
                1, kvh, -1, 1, s_len)                # (1, kvh, g, 1, S)
        m = jnp.max(s, axis=-1, keepdims=True)
        un = jnp.exp(s - m)
        denom = jnp.sum(un, axis=-1, keepdims=True)
        p = un / denom
        # literally the dense path's PV on this slot's rows, with the
        # page-major scratch flattened back to the dense S axis
        v = vb_ref[...].reshape(1, s_len, kvh, -1)   # (1, S, kvh, d)
        if sc_bits is not None:
            # same operand alignment as the dense SC decode path: v rows
            # keyed (1, kvh, 1, 1, S, d) against p (1, kvh, g, 1, S)
            out = sc_pv(p, v.transpose(0, 2, 1, 3)[:, :, None, None],
                        bits=sc_bits)                # (1, kvh, g, 1, d)
        else:
            out = jnp.einsum("bcgqk,bkcd->bcgqd", p, v,  # fp32, dense PV
                             preferred_element_type=jnp.float32)
        o_ref[0] = out[0, :, :, 0].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "logit_softcap",
                                             "kvh", "interpret", "sc_bits"))
def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           q_positions: jax.Array, *,
                           window: int | None = None,
                           logit_softcap: float | None = None,
                           kvh: int = 1,
                           interpret: bool = False,
                           sc_bits: int | None = None) -> jax.Array:
    """``q: (C, KV, G, D)``; ``k_pages, v_pages: (P, block, KV, D)``;
    ``tables: (C, MB) int32`` (−1 = unallocated); ``q_positions: (C,)``.

    Returns ``(C, KV, G, D)`` — bit-identical to gathering the pages dense
    and running :func:`repro.models.layers.decode_attention` (with the same
    ``sc_bits``). ``kvh`` must divide KV (autotuned via
    :class:`~repro.kernels.autotune.PagedFlashConfig`). ``sc_bits`` routes
    the score/PV contractions through the SC popcount path (DESIGN.md §13),
    which carries no head-layout restrictions.
    """
    c, kv, g, d = q.shape
    n_pages, block, _, _ = k_pages.shape
    max_blocks = tables.shape[1]
    trash = n_pages - 1
    scale = d ** -0.5
    if kv % kvh != 0:
        # a non-dividing kvh would truncate the head grid and return
        # uninitialized output rows for the remainder — fail loudly instead
        raise ConfigError(
            f"paged kernel: kvh must divide the KV head count: got "
            f"kvh={kvh}, KV={kv}")
    if g == 1 and kvh == 1 and sc_bits is None:
        # the full-MHA whole-row einsum only reproduces the dense bits when
        # the grid step carries >= 2 KV heads (a single-head slice lowers to
        # a different contraction) — candidate_paged_configs never proposes
        # this point; refuse direct calls rather than return close-but-off.
        # The SC path has no such restriction: its contraction is an
        # elementwise integer popcount sum, insensitive to head layout.
        raise ConfigError("full-MHA (G == 1) requires kvh >= 2 for "
                          "bit-identity on the float path; got kvh=1")

    def qmap(ci, hi, ji, tbl, qp):
        return (ci, hi, 0, 0)

    def kvmap(ci, hi, ji, tbl, qp):
        page = tbl[ci, ji]
        # unallocated → trash block, exactly like cache_ops._safe_tables
        return (jnp.where(page < 0, trash, page), 0, hi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c, kv // kvh, max_blocks),
        in_specs=[
            pl.BlockSpec((1, kvh, g, d), qmap),
            pl.BlockSpec((1, block, kvh, d), kvmap),
            pl.BlockSpec((1, block, kvh, d), kvmap),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, d), qmap),
        scratch_shapes=[
            # Float g >= 2 and every SC layout: masked per-page score tiles.
            # Float g == 1 (full-MHA): raw K pages in the cache dtype —
            # scoring happens whole-row at the finish step (see _kernel),
            # so no cast may touch K before it.
            pltpu.VMEM((max_blocks, block, kvh, d), k_pages.dtype)
            if (g == 1 and sc_bits is None) else
            pltpu.VMEM((max_blocks, kvh, g, block), jnp.float32),
            pltpu.VMEM((max_blocks, block, kvh, d), jnp.float32),  # fp32 V
        ],
    )
    kernel = functools.partial(_kernel, block, max_blocks, scale, window,
                               logit_softcap, sc_bits)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), q_positions.astype(jnp.int32),
      q, k_pages, v_pages)
