"""Assigned input-shape set (LM family): every shape applies to every arch,
with the documented exceptions (long_500k only for sub-quadratic archs).

``input_specs`` builds jax.ShapeDtypeStruct stand-ins for the dry-run — no
device allocation, weak-type-correct, shardable.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .base import ModelConfig

__all__ = ["Shape", "SHAPES", "input_specs", "cache_specs", "is_applicable",
           "sc_gemm_problems"]


@dataclass(frozen=True)
class Shape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


SHAPES = {
    "train_4k": Shape("train_4k", 4_096, 256, "train"),
    "prefill_32k": Shape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": Shape("decode_32k", 32_768, 128, "decode"),
    "long_500k": Shape("long_500k", 524_288, 1, "decode"),
}

#: archs allowed to run long_500k (sub-quadratic decode state growth)
_SUBQUADRATIC_FAMILIES = {"ssm", "hybrid"}


def is_applicable(cfg: ModelConfig, shape: Shape) -> tuple[bool, str]:
    """(runnable?, reason-if-not). Per spec: long_500k is skipped for pure
    full-attention archs; all assigned archs are decoders so decode always runs."""
    if shape.name == "long_500k" and cfg.family not in _SUBQUADRATIC_FAMILIES:
        return False, (f"{cfg.name} is (or contains) full quadratic attention; "
                       "long_500k requires sub-quadratic decode (spec: run for "
                       "SSM/hybrid only)")
    return True, ""


def _token_spec(cfg: ModelConfig, batch: int, seq: int):
    if cfg.n_codebooks:
        return jax.ShapeDtypeStruct((batch, seq, cfg.n_codebooks), jnp.int32)
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32)


def input_specs(cfg: ModelConfig, shape: Shape, *,
                visual_patches: int = 1024) -> dict:
    """ShapeDtypeStruct stand-ins for one step's inputs.

    train/prefill: the full (batch, seq) token block (+labels for train).
    decode: one new token per sequence (the KV/SSM cache is a separate spec).
    """
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        specs = {"tokens": _token_spec(cfg, b, s),
                 "labels": _token_spec(cfg, b, s)}
    elif shape.kind == "prefill":
        specs = {"tokens": _token_spec(cfg, b, s)}
    else:  # decode: one token against a seq_len-deep cache
        specs = {"tokens": _token_spec(cfg, b, 1)}

    if cfg.family == "vlm" and shape.kind != "decode":
        specs["visual_embeds"] = jax.ShapeDtypeStruct(
            (b, min(visual_patches, s // 4), cfg.d_model), jnp.bfloat16)
        specs["mrope_positions"] = jax.ShapeDtypeStruct((3, b, s), jnp.int32)
    return specs


def sc_gemm_problems(cfg: ModelConfig, shape: Shape) -> list[tuple[int, int, int]]:
    """Distinct (M, K, N) SC-GEMM problems a forward at this shape routes
    through ``sc_dense`` when ``cfg.use_sc_gemm`` (DESIGN.md §6).

    M is the token count the projection sees (one new token per sequence for
    decode); the K/N pairs enumerate the per-layer dense projections —
    attention QKV/O, the (gated) MLP, Mamba in/out, per-expert FFN rows, and
    the chunked LM head. Used to pre-warm the autotune cache and by the
    count-identity dispatch tests.
    """
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    d = cfg.d_model
    probs: set[tuple[int, int, int]] = set()
    if cfg.mla:
        h, r = cfg.n_heads, cfg.kv_lora_rank
        probs.add((tokens, d, h * cfg.head_dim))                # wq
        probs.add((tokens, d, r + cfg.qk_rope_head_dim))        # wkv_a
        probs.add((tokens, h * cfg.v_head_dim, d))              # wo
    elif cfg.family != "ssm":
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        probs.add((tokens, d, h * hd))          # wq
        probs.add((tokens, d, kv * hd))         # wk, wv
        probs.add((tokens, h * hd, d))          # wo
    if cfg.d_ff:
        probs.add((tokens, d, cfg.d_ff))        # w1, w3
        probs.add((tokens, cfg.d_ff, d))        # w2
    if cfg.n_experts and cfg.moe_d_ff:
        rows = tokens * cfg.top_k   # the dropless layout: every row, each expert
        probs.add((rows, d, cfg.moe_d_ff))
        probs.add((rows, cfg.moe_d_ff, d))
        if cfg.shared_expert_d_ff:
            probs.add((tokens, d, cfg.shared_expert_d_ff))
            probs.add((tokens, cfg.shared_expert_d_ff, d))
    if cfg.ssm_state:
        d_in = cfg.d_inner
        proj_out = 2 * d_in + 2 * cfg.ssm_state + cfg.ssm_heads
        probs.add((tokens, d, proj_out))        # in_proj
        probs.add((tokens, d_in, d))            # out_proj
    head_rows = (shape.global_batch * min(cfg.loss_chunk, shape.seq_len)
                 if shape.kind == "train" else shape.global_batch)
    head_out = cfg.vocab_size * max(cfg.n_codebooks, 1)
    probs.add((head_rows, d, head_out))         # lm head (loss-chunked)
    return sorted(probs)


def cache_specs(cfg: ModelConfig, shape: Shape):
    """ShapeDtypeStruct pytree for the decode cache at this shape."""
    from repro.models import bind
    m = bind(cfg)
    cache = jax.eval_shape(lambda: m.init_cache(shape.global_batch, shape.seq_len))
    return cache
