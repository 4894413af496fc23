"""Generic decoder-only transformer covering the dense / MoE / VLM / audio
assigned architectures (qwen2*, gemma2, smollm, musicgen, qwen3-moe, llama4,
qwen2-vl).

Depth is executed as ``lax.scan`` over *groups* of layers: a group is one
period of the config's window/MoE pattern (1 for uniform models, 2 for
gemma2's local/global alternation, 4 for llama4's chunked+MoE interleave).
Parameters are stacked over groups, so HLO size is depth-independent and
activation remat is one `jax.checkpoint` per group.

SC-GEMM integration (the paper's numeric): with ``cfg.use_sc_gemm`` every
dense projection — QKV/O, MLP, and the LM head — runs through
``repro.core.sc_layers.sc_dense`` (forward through the stochastic multiplier
GEMM, straight-through gradients), with the kernel implementation picked by
``cfg.sc_impl`` via the DESIGN.md §6 dispatch (config → $REPRO_SC_IMPL →
backend/autotune cache).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sc_layers import sc_proj
from repro.parallel.context import shard_activations
from .layers import (PagedKV, _gather_pages, apply_mrope, apply_rope,
                     deinterleave, decode_attention, flash_attention,
                     paged_decode_attention, rms_norm, rope, rope_inv_freq,
                     softcap, yarn_mscale)
from .moe import N_STATS, init_moe_params, moe_ffn

__all__ = ["init_params", "forward_hidden", "loss_fn", "init_kv_cache",
           "decode_step", "paged_decode_step", "decode_window_step",
           "logits_from_hidden"]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ----------------------------------------------------------------- params

def _init_attn(cfg: ModelConfig, key, dtype) -> dict:
    """QKV/O weights kept 3D — (d, heads, head_dim) — so the head axis is an
    explicit, GSPMD-shardable dimension (flattened h·hd would split mid-head
    for head counts not divisible by the model-axis size)."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": (jax.random.normal(ks[0], (d, h, hd)) * d ** -0.5).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv, hd)) * d ** -0.5).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv, hd)) * d ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h, hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _init_mla(cfg: ModelConfig, key, dtype) -> dict:
    """Latent attention weights (DeepSeek-V2, no query compression):
    ``wq (d, H, nope + rope)``, ``wkv_a (d, rank + rope)`` giving the latent
    ``c_kv`` and the shared rope key, ``kv_norm (rank,)``, ``wkv_b (rank, H,
    nope + v)`` decompressing ``c_kv`` into each head's key and value, and
    ``wo (H, v, d)``."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": (jax.random.normal(ks[0], (d, h, nope + rp)) * d ** -0.5
               ).astype(dtype),
        "wkv_a": (jax.random.normal(ks[1], (d, r + rp)) * d ** -0.5
                  ).astype(dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": (jax.random.normal(ks[2], (r, h, nope + vd)) * r ** -0.5
                  ).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h, vd, d)) * (h * vd) ** -0.5
               ).astype(dtype),
    }


def _init_mlp(cfg: ModelConfig, key, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": (jax.random.normal(k1, (d, f)) * d ** -0.5).astype(dtype),
        "w3": (jax.random.normal(k2, (d, f)) * d ** -0.5).astype(dtype),
        "w2": (jax.random.normal(k3, (f, d)) * f ** -0.5).astype(dtype),
    }


def _init_layer(cfg: ModelConfig, pos: int, key, dtype, *,
                dense: bool = False) -> dict:
    ka, kf = jax.random.split(key)
    layer = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": (_init_mla if cfg.mla else _init_attn)(cfg, ka, dtype),
    }
    if cfg.post_norms:
        layer["ln1_post"] = jnp.ones((cfg.d_model,), dtype)
        layer["ln2_post"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.moe_at(pos) and not dense:
        layer["moe"] = init_moe_params(cfg, kf, dtype)
    else:
        layer["mlp"] = _init_mlp(cfg, kf, dtype)
    return layer


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    cfg.validate()
    dtype = _dtype(cfg)
    gsz = cfg.group_size
    ngroups = cfg.scan_layers // gsz
    k_emb, k_head, k_layers = jax.random.split(key, 3)

    if cfg.n_codebooks:
        embed = (jax.random.normal(k_emb, (cfg.n_codebooks, cfg.vocab_size, cfg.d_model))
                 * cfg.d_model ** -0.5)
        head_out = cfg.n_codebooks * cfg.vocab_size
    else:
        embed = jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * cfg.d_model ** -0.5
        head_out = cfg.vocab_size

    params: dict[str, Any] = {
        "embed": embed.astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, head_out))
                             * cfg.d_model ** -0.5).astype(dtype)

    def init_group(gkey):
        return tuple(_init_layer(cfg, p, jax.random.fold_in(gkey, p), dtype)
                     for p in range(gsz))

    gkeys = jax.random.split(k_layers, ngroups)
    stacked = jax.vmap(init_group)(gkeys)   # leaves: (ngroups, ...)
    params["layers"] = stacked
    if cfg.first_k_dense:
        # leading dense layers, stacked (first_k_dense, ...), run unrolled
        # before the scan
        lkeys = jax.random.split(jax.random.fold_in(key, 3), cfg.first_k_dense)
        params["lead"] = jax.vmap(
            lambda k: _init_layer(cfg, 0, k, dtype, dense=True))(lkeys)
    return params


# ----------------------------------------------------------------- forward

def _project(x, w, cfg, b=None):
    out = sc_proj(x, w, cfg)
    return out + b if b is not None else out


# ------------------------------------------------- latent attention (MLA)

def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``mscale(factor, mscale_all_dim)²``
    when the rope is YaRN-scaled."""
    scale = cfg.head_dim ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _mla_rope(cfg: ModelConfig, positions) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables ``(B, S, rope / 2)`` of the (YaRN-scaled) rope."""
    inv = jnp.asarray(rope_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, yarn_factor=cfg.yarn_factor,
        original_max_pos=cfg.yarn_original_max_pos,
        beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow))
    ang = positions[..., None].astype(jnp.float32) * inv
    m = 1.0
    if cfg.yarn_factor:
        m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _einsum_f32(eq: str, a, b):
    """``einsum`` accumulated in float32: the operands in their own type
    where the program is lowered for a TPU, cast to float32 elsewhere (the
    CPU has no bfloat16 x bfloat16 -> float32 dot at every shape)."""
    return jax.lax.platform_dependent(
        a, b,
        tpu=lambda a, b: jnp.einsum(eq, a, b,
                                    preferred_element_type=jnp.float32),
        default=lambda a, b: jnp.einsum(eq, a.astype(jnp.float32),
                                        b.astype(jnp.float32)))


def _per_head(x, w):
    """``x (B, T, H, i)`` times each head's ``w (i, H, o)`` -> ``(B, T, H,
    o)``: the absorbed ``W_UK`` / ``W_UV`` products. Exact under every
    numeric: the multiplier quantizes its activation operand per row, and
    the absorbed operands (a head's query, its weighted latent) are not the
    published ``c_kv W_kv_b``'s, so no SC form of them can match the
    reference's; the reference keeps that product exact too."""
    return _einsum_f32("bthi,iho->btho", x, w).astype(x.dtype)


def _mla_attend(q_lat, q_pe, c, k_pe, q_pos, kv_pos, scale):
    """Absorbed latent attention: ``q_lat (B, T, H, rank)`` and ``q_pe (B,
    T, H, rope)`` against the latent cache ``c (B, E, rank)`` and the shared
    rope keys ``k_pe (B, E, rope)``; a key at position ``kv_pos (B, E)``
    is seen by a query at ``q_pos (B, T)`` at or after it. One exact
    float32 softmax per row; returns the probability-weighted latent ``(B,
    T, H, rank)`` in float32."""
    s = (_einsum_f32("bthr,ber->bhte", q_lat, c)
         + _einsum_f32("bthp,bep->bhte", q_pe, k_pe)) * scale
    mask = kv_pos[:, None, :] <= q_pos[:, :, None]              # (B, T, E)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # the probabilities in the cache's type: a TPU rounds a float32 operand
    # so anyway, and no float32 copy of the latent cache is made
    return _einsum_f32("bhte,ber->bthr", p.astype(c.dtype), c)


def _mla_forward(p: dict, x: jax.Array, cfg: ModelConfig, *, positions,
                 cache, cache_pos, decode_window: bool = False):
    """Multi-head latent attention (DeepSeek-V2) over a latent cache.

    A token stores ``c_kv`` (``kv_lora_rank``, RMS-normed) and its rope key
    (``qk_rope_head_dim``, shared by every head) — no head axis — in the
    cache's ``k`` and ``v`` leaves. Every path attends in the absorbed
    form: the query's no-rope part is taken into the latent space through
    each head's ``W_UK`` (the key half of ``wkv_b``), scored against
    ``c_kv`` plus the rope dot product, the probabilities are summed over
    ``c_kv``, and each head's ``W_UV`` then gives its value. The cache is
    never decompressed. Cache kinds as in :func:`_attn_forward`: none,
    ``"collect"``, a :class:`PagedKV` pool (decode), or a dense ``(c, k_pe)``
    pair (chunked prefill at the cache's offset, one-row decode, or a
    ``decode_window`` of rows)."""
    b, s, d = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = sc_proj(x, p["wq"].reshape(d, h * cfg.head_dim), cfg
                ).reshape(b, s, h, cfg.head_dim)
    kv = sc_proj(x, p["wkv_a"], cfg)                        # (B, S, r + rope)
    c = rms_norm(kv[..., :r], p["kv_norm"], eps=cfg.norm_eps)
    cos, sin = _mla_rope(cfg, positions)
    q_pe = apply_rope(deinterleave(q[..., nope:]), cos, sin)
    k_pe = apply_rope(deinterleave(kv[..., r:])[:, :, None], cos, sin)[:, :, 0]
    q_lat = _per_head(q[..., :nope],
                      p["wkv_b"][..., :nope].transpose(2, 1, 0))  # (B, S, H, r)

    if isinstance(cache, PagedKV):
        from .cache_ops import paged_token_entry
        cache_pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        entry, off = paged_token_entry(cache.tables, cache_pos,
                                       block=cache.block)
        bid = jnp.where(entry < 0, cache.trash, entry)
        new_cache = PagedKV(
            cache.k.at[bid, off].set(c[:, 0].astype(cache.k.dtype)),
            cache.v.at[bid, off].set(k_pe[:, 0].astype(cache.v.dtype)),
            cache.tables)
        c_all = _gather_pages(new_cache.k, cache.tables)
        pe_all = _gather_pages(new_cache.v, cache.tables)
        q_pos, kv_pos = cache_pos[:, None], None
    elif cache is not None and cache != "collect":
        c_all, pe_all = cache
        cache_pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        if decode_window or s == 1:
            # rows at each sequence's own positions; a free slot's drifted
            # position past the cache drops
            rows = jnp.arange(b)[:, None]
            wpos = cache_pos[:, None] + jnp.arange(s)[None, :]
            c_all = c_all.at[rows, wpos].set(c.astype(c_all.dtype),
                                             mode="drop")
            pe_all = pe_all.at[rows, wpos].set(k_pe.astype(pe_all.dtype),
                                               mode="drop")
        else:
            # a prefill chunk at the staging cache's shared offset
            start = (jnp.zeros((), jnp.int32), cache_pos[0],
                     jnp.zeros((), jnp.int32))
            c_all = jax.lax.dynamic_update_slice(
                c_all, c.astype(c_all.dtype), start)
            pe_all = jax.lax.dynamic_update_slice(
                pe_all, k_pe.astype(pe_all.dtype), start)
        new_cache = (c_all, pe_all)
        q_pos, kv_pos = positions, None
    else:
        # the sequence attends to itself, at its own positions
        c_all, pe_all, q_pos, kv_pos = c, k_pe, positions, positions
        new_cache = (c, k_pe) if cache == "collect" else None
    if kv_pos is None:      # a cache: entry i holds position i
        e = c_all.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32), (b, e))
    o_lat = _mla_attend(q_lat.astype(c_all.dtype), q_pe.astype(pe_all.dtype),
                        c_all, pe_all, jnp.broadcast_to(q_pos, (b, s)),
                        kv_pos, mla_softmax_scale(cfg))
    out = _per_head(o_lat.astype(x.dtype), p["wkv_b"][..., nope:])
    o = sc_proj(out.reshape(b, s, h * vd), p["wo"].reshape(h * vd, d), cfg)
    return o, new_cache


def _attn_forward(p: dict, x: jax.Array, cfg: ModelConfig, *,
                  window: int | None, positions, mrope_positions,
                  cache: tuple | None, cache_pos,
                  canonical_positions: bool = True,
                  decode_window: bool = False) -> tuple[jax.Array, tuple | None]:
    if cfg.mla:
        return _mla_forward(p, x, cfg, positions=positions, cache=cache,
                            cache_pos=cache_pos, decode_window=decode_window)
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # one resolution point for every attention call below, so the prefill,
    # dense-decode, and paged-decode paths can never disagree on the numeric
    attn_sc_bits = cfg.sc_bits if cfg.attn_sc else None

    def proj(w, bias):
        # (d, heads, hd) is a matmul with the head axes flattened; route it
        # through the sc_proj dispatch like every other dense projection.
        _, nh, _ = w.shape
        out = sc_proj(x, w.reshape(d, nh * hd), cfg).reshape(b, s, nh, hd)
        return out + bias if bias is not None else out

    q = proj(p["wq"], p.get("bq"))
    k = proj(p["wk"], p.get("bk"))
    v = proj(p["wv"], p.get("bv"))

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], eps=cfg.norm_eps)

    if cfg.mrope_sections is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        cos, sin = rope(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if isinstance(cache, PagedKV):
        # fused paged decode (DESIGN.md §9): scatter this token's K/V
        # straight into its page — (tables[slot, pos // block], pos % block),
        # the same cell paged_commit would target — then attend against the
        # page pool itself (in-kernel table walk, or the per-layer gathered
        # view for ineligible layouts). No dense round-trip exists to drift
        # from: a free slot's table entry is -1, so its drifted-position
        # write lands in the trash block, which only masked reads ever see.
        from .cache_ops import paged_token_entry
        cache_pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        entry, off = paged_token_entry(cache.tables, cache_pos,
                                       block=cache.block)
        bid = jnp.where(entry < 0, cache.trash, entry)
        k_pages = cache.k.at[bid, off].set(k[:, 0].astype(cache.k.dtype))
        v_pages = cache.v.at[bid, off].set(v[:, 0].astype(cache.v.dtype))
        new_paged = PagedKV(k_pages, v_pages, cache.tables)
        out = paged_decode_attention(q, new_paged, q_position=cache_pos,
                                     window=window,
                                     logit_softcap=cfg.attn_softcap,
                                     kernel_impl=cfg.paged_attn_kernel,
                                     sc_bits=attn_sc_bits)
        new_cache = new_paged
    elif cache is not None and cache != "collect":
        k_cache, v_cache = cache
        cache_pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        if decode_window:
            # speculative verify (DESIGN.md §14): scatter the whole
            # ``s``-row window's K/V at each slot's own positions
            # ``[pos, pos + s)`` — a per-slot generalization of the one-row
            # decode scatter below, with the same mode="drop" semantics for
            # free slots whose drifted window leaves the cache view — then
            # run the W-row exact-softmax decode attention. Never the flash
            # path: its online softmax re-rounds, and verification's whole
            # point is matching the sequential decode numerics row-for-row.
            batch_idx = jnp.arange(b)[:, None]
            wpos = cache_pos[:, None] + jnp.arange(s)[None, :]
            k_cache = k_cache.at[batch_idx, wpos].set(k, mode="drop")
            v_cache = v_cache.at[batch_idx, wpos].set(v, mode="drop")
            out = decode_attention(q, k_cache, v_cache, q_position=cache_pos,
                                   window=window,
                                   logit_softcap=cfg.attn_softcap,
                                   sc_bits=attn_sc_bits)
        elif s > 1:
            # chunked prefill: scatter a whole chunk's K/V at the shared
            # per-batch offset (the staging cache is B=1; all rows sit at the
            # same position) and flash-attend with *absolute* positions —
            # q rows at [off, off+s), kv columns over the cache's full
            # bucket extent. Columns past the filled prefix are causally
            # masked (their positions exceed every valid q row), so the
            # bucket padding and any garbage pad-row writes are exact
            # no-ops for the valid rows; explicit positions force the jnp
            # flash path, the same one a short one-shot prefill takes.
            off = cache_pos[0]
            start = (jnp.zeros((), jnp.int32), off) + \
                (jnp.zeros((), jnp.int32),) * 2
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), start)
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), start)
            e = k_cache.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32), (b, e))
            out = flash_attention(
                q, k_cache, v_cache, q_positions=positions,
                kv_positions=kv_pos, causal=True, window=window,
                logit_softcap=cfg.attn_softcap, q_block=min(cfg.q_block, s),
                kv_block=min(cfg.kv_block, e), skip_masked_blocks=False,
                bf16_probs=cfg.bf16_probs, kernel_impl=cfg.attn_kernel,
                canonical_positions=False, sc_bits=attn_sc_bits)
        else:
            # decode: write this token's K/V at each sequence's own position.
            # ``cache_pos: (B,)`` — per-sequence absolute positions, so
            # sequences admitted at different times (serving slot pool,
            # DESIGN.md §7) share one batched step. The cache rows may be a
            # paged-gather view (DESIGN.md §8) whose sequence extent is a
            # page-count multiple, not max_seq; mode="drop" makes the
            # free-slot behaviour explicit — an idle serving slot's position
            # can drift past the view and its write must vanish rather than
            # clamp onto a live row's tail.
            batch_idx = jnp.arange(b)
            k_cache = k_cache.at[batch_idx, cache_pos].set(k[:, 0], mode="drop")
            v_cache = v_cache.at[batch_idx, cache_pos].set(v[:, 0], mode="drop")
            out = decode_attention(q, k_cache, v_cache, q_position=cache_pos,
                                   window=window,
                                   logit_softcap=cfg.attn_softcap,
                                   sc_bits=attn_sc_bits)
        new_cache = (k_cache, v_cache)
    else:
        if cfg.attn_kv_gather:
            # §Perf: force K/V into the gathered-once layout so the flash
            # loops slice locally instead of re-gathering per block step
            from jax.sharding import PartitionSpec as _P
            from repro.parallel.context import batch_axes, constrain
            baxes = batch_axes()
            k = constrain(k, _P(baxes, None, None, None))
            v = constrain(v, _P(baxes, None, None, None))
        out = flash_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=True, window=window, logit_softcap=cfg.attn_softcap,
            q_block=min(cfg.q_block, s), kv_block=min(cfg.kv_block, s),
            skip_masked_blocks=cfg.skip_masked_blocks,
            bf16_probs=cfg.bf16_probs, kernel_impl=cfg.attn_kernel,
            canonical_positions=canonical_positions, sc_bits=attn_sc_bits)
        new_cache = (k, v) if cache == "collect" else None

    o = sc_proj(out.reshape(b, s, h * hd), p["wo"].reshape(h * hd, d), cfg)
    return o, new_cache


def _mlp_forward(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(_project(x, p["w1"], cfg)) * _project(x, p["w3"], cfg)
    return _project(h, p["w2"], cfg)


def _layer_forward(layer: dict, x: jax.Array, cfg: ModelConfig, pos: int, *,
                   positions, mrope_positions, cache, cache_pos,
                   canonical_positions: bool = True,
                   decode_window: bool = False):
    window = cfg.window_at(pos)
    attn_in = rms_norm(x, layer["ln1"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    attn_out, new_cache = _attn_forward(
        layer["attn"], attn_in, cfg, window=window, positions=positions,
        mrope_positions=mrope_positions, cache=cache, cache_pos=cache_pos,
        canonical_positions=canonical_positions, decode_window=decode_window)
    if cfg.post_norms:
        attn_out = rms_norm(attn_out, layer["ln1_post"], eps=cfg.norm_eps,
                            plus_one=cfg.norm_plus_one)
    x = x + attn_out

    ff_in = rms_norm(x, layer["ln2"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    aux = jnp.float32(0.0)
    stats = jnp.zeros((N_STATS,), jnp.int32)
    if "moe" in layer:
        ff_out, aux, stats = moe_ffn(layer["moe"], ff_in, cfg)
    else:
        ff_out = _mlp_forward(layer["mlp"], ff_in, cfg)
    if cfg.post_norms:
        ff_out = rms_norm(ff_out, layer["ln2_post"], eps=cfg.norm_eps,
                          plus_one=cfg.norm_plus_one)
    return x + ff_out, new_cache, aux, stats


def _lead_layers(params: dict, cfg: ModelConfig, x: jax.Array, caches, **kw):
    """The leading dense layers (``cfg.first_k_dense``), unrolled before the
    scan. ``caches`` is None or each layer's cache argument. Returns ``(x,
    new caches, aux)``."""
    new, aux = [], jnp.float32(0.0)
    for i in range(cfg.first_k_dense):
        layer = jax.tree.map(lambda a: a[i], params["lead"])
        x, c, a, _ = _layer_forward(layer, x, cfg, 0,
                                    cache=None if caches is None else caches[i],
                                    **kw)
        new.append(c)
        aux += a
    return x, new, aux


def _lead_cache(cfg: ModelConfig, cache) -> list:
    """Each leading layer's ``(k, v)`` leaves: the leading layers' stacks
    follow the group positions in the cache's ``k``/``v`` tuples."""
    if not cfg.first_k_dense:
        return []
    lk, lv = cache.k[cfg.group_size], cache.v[cfg.group_size]
    return [(lk[i], lv[i]) for i in range(cfg.first_k_dense)]


def _stack_lead(new: list) -> tuple:
    """The leading layers' new ``(k, v)`` pairs -> the ``(k stack,)``,
    ``(v stack,)`` tails of the cache's tuples (empty without them)."""
    if not new:
        return (), ()
    return ((jnp.stack([c[0] for c in new]),),
            (jnp.stack([c[1] for c in new]),))


#: The routed experts' weights, which the scans keep out of their per-layer
#: slices (see :func:`_scan_params`).
_EXPERT_LEAVES = ("w1", "w3", "w2")


def _scan_params(layers) -> tuple:
    """Split the stacked group params for a scan over the groups: the
    per-group slices without the routed experts' weights, and those weights
    as whole stacks per group position. The grouped matmul reads its
    layer's experts straight out of the stack (``moe.moe_ffn`` with a
    ``layer`` index): a slice of them taken inside the scan would be copied
    in every step, about three times the expert bytes a step must read."""
    xs, stacks = [], []
    for pos_params in layers:
        moe = pos_params.get("moe")
        if moe is None:
            xs.append(pos_params)
            stacks.append(None)
            continue
        xs.append({**pos_params, "moe": {k: v for k, v in moe.items()
                                         if k not in _EXPERT_LEAVES}})
        stacks.append({k: moe[k] for k in _EXPERT_LEAVES})
    return tuple(xs), stacks


def _group_params(group_params, stacks, g) -> tuple:
    """One group's params inside the scan, the routed experts given as
    their stacks and the group index ``g``."""
    return tuple(p if st is None else
                 {**p, "moe": {**p["moe"], **st, "layer": g}}
                 for p, st in zip(group_params, stacks))


def _count(cache, stats):
    """The cache's MoE load counters advanced by ``stats`` (scan outputs
    ``(ngroups, N_STATS)``); None for a cache without them."""
    if cache.counts is None:
        return None
    return cache.counts + stats.sum(axis=0).astype(cache.counts.dtype)


def _embed_tokens(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        # musicgen: (B, S, K) codebook ids; frontend stub sums codebook embeds
        parts = [jnp.take(params["embed"][i], tokens[..., i], axis=0)
                 for i in range(cfg.n_codebooks)]
        x = sum(parts)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.emb_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if "visual_embeds" in batch and batch["visual_embeds"] is not None:
        vis = batch["visual_embeds"].astype(x.dtype)   # (B, P, d) patch stub
        x = jax.lax.dynamic_update_slice(x, vis, (0, 0, 0))
    return x


def forward_hidden(params: dict, cfg: ModelConfig, batch: dict) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward -> (hidden (B,S,d) after final norm, aux loss)."""
    x = _embed_tokens(params, cfg, batch)
    b, s, _ = x.shape
    positions = batch.get("positions_1d")
    canonical = positions is None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mrope_positions = batch.get("mrope_positions")

    gsz = cfg.group_size
    x, _, aux_lead = _lead_layers(params, cfg, x, None, positions=positions,
                                  mrope_positions=mrope_positions,
                                  cache_pos=None,
                                  canonical_positions=canonical)

    xs, stacks = _scan_params(params["layers"])

    def group_body(x, inputs):
        x = shard_activations(x)
        group_params = _group_params(inputs[0], stacks, inputs[1])
        aux_total = jnp.float32(0.0)
        for pos in range(gsz):
            x, _, aux, _ = _layer_forward(group_params[pos], x, cfg, pos,
                                          positions=positions,
                                          mrope_positions=mrope_positions,
                                          cache=None, cache_pos=None,
                                          canonical_positions=canonical)
            aux_total += aux
        return x, aux_total

    body = jax.checkpoint(group_body) if cfg.remat else group_body
    x, auxes = jax.lax.scan(lambda c, p: body(c, p), x,
                            (xs, jnp.arange(len(xs[0]["ln1"]))))
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    return x, auxes.sum() + aux_lead


def logits_from_hidden(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    head = params["lm_head"] if "lm_head" in params else (
        params["embed"].T if not cfg.n_codebooks else
        jnp.transpose(params["embed"], (2, 0, 1)).reshape(cfg.d_model, -1))
    logits = sc_proj(hidden, head, cfg)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.n_codebooks:
        logits = logits.reshape(*hidden.shape[:-1], cfg.n_codebooks, cfg.vocab_size)
    return logits


def loss_fn(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Next-token CE, computed in sequence chunks so (B, S, V) never
    materializes (V up to 256k). Aux (MoE balance) loss folded in."""
    hidden, aux = forward_hidden(params, cfg, batch)
    labels = batch["labels"]
    b, s = labels.shape[:2]
    chunk = min(cfg.loss_chunk, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, [(0, 0), (0, pad)] + [(0, 0)] * (labels.ndim - 2),
                         constant_values=-1)
    nc = (s + pad) // chunk
    hidden = hidden.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
    lab = labels.reshape(b, nc, chunk, *labels.shape[2:]).transpose(1, 0, 2,
                                                                    *range(3, labels.ndim + 1))

    def chunk_loss(carry, inputs):
        h, y = inputs
        logits = logits_from_hidden(params, cfg, h)
        valid = (y >= 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        total, count = carry
        return (total + jnp.where(valid, -ll, 0.0).sum(),
                count + valid.sum(dtype=jnp.int32)), None

    (total, count), _ = jax.lax.scan(chunk_loss,
                                     (jnp.float32(0.0), jnp.int32(0)),
                                     (hidden, lab))
    return total / jnp.maximum(count, 1) + 0.01 * aux


# ----------------------------------------------------------------- prefill

def prefill_step(params: dict, cfg: ModelConfig, batch: dict, *,
                 extra_slots: int = 0):
    """Process the full prompt, returning (last-token logits, filled KVCache).

    ``extra_slots`` pads the cache's sequence axis for subsequent decode.
    """
    x = _embed_tokens(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mrope_positions = batch.get("mrope_positions")
    gsz = cfg.group_size
    x, lead, _ = _lead_layers(params, cfg, x, ["collect"] * cfg.first_k_dense,
                              positions=positions,
                              mrope_positions=mrope_positions, cache_pos=None)

    xs, stacks = _scan_params(params["layers"])

    def group_body(x, inputs):
        x = shard_activations(x)
        group_params = _group_params(inputs[0], stacks, inputs[1])
        ks, vs = [], []
        stats = jnp.zeros((N_STATS,), jnp.int32)
        for pos in range(gsz):
            x, kvc, _, st = _layer_forward(group_params[pos], x, cfg, pos,
                                           positions=positions,
                                           mrope_positions=mrope_positions,
                                           cache="collect", cache_pos=None)
            ks.append(kvc[0])
            vs.append(kvc[1])
            stats += st
        return x, (tuple(ks), tuple(vs), stats)

    body = jax.checkpoint(group_body) if cfg.remat else group_body
    x, (ks, vs, stats) = jax.lax.scan(lambda c, p: body(c, p), x,
                                      (xs, jnp.arange(len(xs[0]["ln1"]))))
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = logits_from_hidden(params, cfg, x[:, -1:])

    lk, lv = _stack_lead(lead)
    ks, vs = ks + lk, vs + lv
    if extra_slots:
        def pad(a):
            return jnp.pad(a, [(0, 0), (0, 0), (0, extra_slots)]
                           + [(0, 0)] * (a.ndim - 3))
        ks = tuple(pad(k) for k in ks)
        vs = tuple(pad(v) for v in vs)
    cache = KVCache(k=ks, v=vs, pos=jnp.full((b,), s, jnp.int32),
                    counts=_init_counts(cfg))
    return logits, cache._replace(counts=_count(cache, stats))


def prefill_chunk_step(params: dict, cfg: ModelConfig, cache: "KVCache",
                       batch: dict) -> tuple[jax.Array, "KVCache"]:
    """Commit one prompt chunk into a B=1 staging cache at the cache's
    current position (chunked prefill, DESIGN.md §10).

    ``batch["tokens"]: (1, T)`` is the chunk, zero-padded past
    ``batch["n_valid"]: (1,)`` real tokens (only the final chunk of a prompt
    is ever padded, so full chunks always land contiguously). Returns the
    logits of the last *valid* row — ``(1, 1, V)``, the same row a one-shot
    prefill of the prompt would project — and the cache advanced by
    ``n_valid``. Pad rows write garbage K/V past the prompt, which
    ``cache_ops.truncate_seq`` slices away before pool admission.
    """
    x = _embed_tokens(params, cfg, batch)
    b, t, _ = x.shape
    n_valid = jnp.reshape(jnp.asarray(batch["n_valid"], jnp.int32), (-1,))[0]
    pos = jnp.broadcast_to(cache.pos, (b,))
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    mrope_positions = batch.get("mrope_positions")
    if cfg.mrope_sections is not None and mrope_positions is None:
        mrope_positions = jnp.broadcast_to(positions[None],
                                           (3, b, t)).astype(jnp.int32)

    x, ks, vs, stats = _scan_cached(
        params, cfg, cache, x, lambda k, v: (k, v), positions=positions,
        mrope_positions=mrope_positions, cache_pos=pos,
        canonical_positions=False, shard=True)
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = logits_from_hidden(params, cfg, last)
    return logits, KVCache(k=ks, v=vs, pos=pos + n_valid,
                           counts=_count(cache, stats))


# ------------------------------------------------------------------ decode

class KVCache(NamedTuple):
    """Decode cache. Slot contract (``models.cache_ops``, DESIGN.md §7):
    array leaves carry the batch/slot dimension at axis 1; ``pos`` is a
    per-sequence ``(B,)`` int32 position vector.

    ``k``/``v`` are tuples over the scan group's positions of ``(ngroups,
    B, S, KV, hd)`` leaves, followed, with leading dense layers, by one
    ``(first_k_dense, B, S, ...)`` stack. Under latent attention (MLA) ``k``
    holds each token's ``c_kv`` ``(..., S, kv_lora_rank)`` and ``v`` its
    shared rope key ``(..., S, qk_rope_head_dim)``: no head axis.
    ``counts`` (MoE families) is the pool-wide ``(N_STATS,)`` int32 load
    counters the step programs advance on the device (``moe.moe_ffn``)."""
    k: Any
    v: Any
    pos: jax.Array
    counts: Any = None


def _init_counts(cfg: ModelConfig):
    return jnp.zeros((N_STATS,), jnp.int32) if cfg.n_experts else None


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int) -> KVCache:
    dtype = _dtype(cfg)
    ngroups = cfg.scan_layers // cfg.group_size
    if cfg.mla:
        tails = ((cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,))
    else:
        tails = ((cfg.n_kv_heads, cfg.head_dim),) * 2
    stacks = [ngroups] * cfg.group_size + (
        [cfg.first_k_dense] if cfg.first_k_dense else [])
    k = tuple(jnp.zeros((n, batch, max_seq) + tails[0], dtype)
              for n in stacks)
    v = tuple(jnp.zeros((n, batch, max_seq) + tails[1], dtype)
              for n in stacks)
    return KVCache(k=k, v=v, pos=jnp.zeros((batch,), jnp.int32),
                   counts=_init_counts(cfg))


def _scan_cached(params: dict, cfg: ModelConfig, cache: KVCache, x,
                 layer_cache, *, shard: bool = False, **kw):
    """Every layer over a cache: the leading dense layers, then the scan
    over the groups. ``layer_cache(k_leaf, v_leaf)`` builds what
    ``_attn_forward`` consumes for one layer; ``kw`` goes to each
    ``_layer_forward``. Returns ``(x, k tuple, v tuple, stats)``, the
    tuples in the cache's layout and ``stats`` the scan's ``(ngroups,
    N_STATS)`` MoE counters."""
    gsz = cfg.group_size
    x, lead, _ = _lead_layers(
        params, cfg, x, [layer_cache(k, v) for k, v in _lead_cache(cfg, cache)],
        **kw)

    xs, stacks = _scan_params(params["layers"])

    def group_body(x, inputs):
        if shard:
            x = shard_activations(x)
        group_params = _group_params(inputs["params"], stacks, inputs["g"])
        new_k, new_v = [], []
        stats = jnp.zeros((N_STATS,), jnp.int32)
        for p in range(gsz):
            x, kvc, _, st = _layer_forward(
                group_params[p], x, cfg, p,
                cache=layer_cache(inputs["k"][p], inputs["v"][p]), **kw)
            new_k.append(kvc[0])
            new_v.append(kvc[1])
            stats += st
        return x, (tuple(new_k), tuple(new_v), stats)

    x, (ks, vs, stats) = jax.lax.scan(
        group_body, x,
        {"params": xs, "g": jnp.arange(len(xs[0]["ln1"])),
         "k": cache.k[:gsz], "v": cache.v[:gsz]})
    lk, lv = _stack_lead(lead)
    return x, ks + lk, vs + lv, stats


def _run_decode(params: dict, cfg: ModelConfig, cache: KVCache, batch: dict,
                layer_cache) -> tuple[jax.Array, KVCache]:
    """Shared one-token decode: embed, scan the layer groups, project.

    ``layer_cache(k_leaf, v_leaf)`` builds what ``_attn_forward`` consumes
    for one layer from the scanned cache leaves — a plain ``(k, v)`` dense
    pair for the contiguous layout, a :class:`~repro.models.layers.PagedKV`
    for the paged pool. Everything else (positions, M-RoPE, the scan
    structure, the LM head) is identical between the two layouts, which is
    what keeps their streams bit-identical.
    """
    x = _embed_tokens(params, cfg, batch)
    b = x.shape[0]
    pos = jnp.broadcast_to(cache.pos, (b,))
    positions = pos[:, None]
    mrope_positions = batch.get("mrope_positions")
    if cfg.mrope_sections is not None and mrope_positions is None:
        mrope_positions = jnp.broadcast_to(pos[None, :, None],
                                           (3, b, 1)).astype(jnp.int32)

    x, ks, vs, stats = _scan_cached(
        params, cfg, cache, x, layer_cache, positions=positions,
        mrope_positions=mrope_positions, cache_pos=pos)
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = logits_from_hidden(params, cfg, x)
    return logits, KVCache(k=ks, v=vs, pos=pos + 1,
                           counts=_count(cache, stats))


def decode_step(params: dict, cfg: ModelConfig, cache: KVCache,
                batch: dict) -> tuple[jax.Array, KVCache]:
    """One token for every sequence in the batch. ``batch["tokens"]: (B, 1)``
    (or (B, 1, K) for codebooks). Returns (logits, updated cache).

    ``cache.pos`` is per-sequence, so co-batched sequences may sit at
    different positions (continuous batching)."""
    return _run_decode(params, cfg, cache, batch, lambda k, v: (k, v))


def paged_decode_step(params: dict, cfg: ModelConfig, cache: KVCache,
                      tables: jax.Array, batch: dict
                      ) -> tuple[jax.Array, KVCache]:
    """One token for every slot, straight on the *paged* pool (DESIGN.md §9).

    ``cache`` is the ``cache_ops.paged_init`` layout — ``k``/``v`` leaves
    are page pools ``(ngroups, P, block, KV, hd)`` — and ``tables`` the
    shared ``(capacity, max_blocks)`` block table. Each layer scatters its
    token into its page and attends through the table
    (``layers.paged_decode_attention``); the ``capacity × max_seq`` dense
    view of the gather/commit round-trip never exists.
    """
    return _run_decode(params, cfg, cache, batch,
                       lambda k, v: PagedKV(k, v, tables))


def decode_window_step(params: dict, cfg: ModelConfig, cache: KVCache,
                       batch: dict) -> tuple[jax.Array, KVCache]:
    """``W`` consecutive tokens for every sequence in one forward — the
    exact-path verification step of speculative decoding (DESIGN.md §14).

    ``batch["tokens"]: (B, W)`` holds each sequence's last sampled token
    followed by its ``W - 1`` draft proposals; rows enter at positions
    ``[cache.pos, cache.pos + W)``. Row ``i`` of the returned logits
    ``(B, W, V)`` is the exact model's next-token distribution after
    consuming rows ``0..i`` — each row causally masks the window's later
    rows through the per-row position mask, and every row gets its own
    exact fp32 softmax (never an online-softmax carry), so row ``i``
    matches what ``i + 1`` sequential :func:`decode_step` calls would
    produce on the same prefix. K/V for all ``W`` rows is written at the
    absolute positions; the engine commits the window to pages and then
    rewinds whatever verification rejects (``cache_ops.paged_rollback``).
    """
    x = _embed_tokens(params, cfg, batch)
    b, w, _ = x.shape
    pos = jnp.broadcast_to(cache.pos, (b,))
    positions = pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    mrope_positions = batch.get("mrope_positions")
    if cfg.mrope_sections is not None and mrope_positions is None:
        mrope_positions = jnp.broadcast_to(positions[None],
                                           (3, b, w)).astype(jnp.int32)

    x, ks, vs, stats = _scan_cached(
        params, cfg, cache, x, lambda k, v: (k, v), positions=positions,
        mrope_positions=mrope_positions, cache_pos=pos, decode_window=True)
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = logits_from_hidden(params, cfg, x)
    return logits, KVCache(k=ks, v=vs, pos=pos + w,
                           counts=_count(cache, stats))
