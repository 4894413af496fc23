"""Mixture-of-Experts FFN: dropless per-token top-k routing.

Every token picks its ``top_k`` experts from a float32 softmax over the
router logits and is computed by each of them: nothing is dropped, so a
token's output depends on the token alone, never on the batch it shares a
step with (which is what lets a request's output be served and cached
exactly). ``cfg.norm_topk_prob`` renormalises the chosen gates to sum to one
(qwen3); without it they stay the softmax probabilities (DeepSeek-V2).

The routed experts run as one grouped matmul over the ``tokens × top_k``
rows sorted by expert, with group sizes from the routing
(``kernels.grouped_matmul``: the TPU kernel or ``lax.ragged_dot``), so the
work is proportional to the rows routed. With ``cfg.use_sc_gemm`` each
expert's matmuls go through ``sc_proj`` instead, vmapped over a padded
dropless layout (per-expert capacity = every row). Shared experts are one
dense gated MLP added to every token. A Switch load-balance aux loss is
returned for training, and per-call load counters for serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sc_layers import sc_proj
from repro.kernels.grouped_matmul import grouped_matmul

__all__ = ["init_moe_params", "moe_ffn", "route", "N_STATS"]

#: Load counters ``moe_ffn`` returns per call: (token, expert) rows computed,
#: and the rows of the busiest expert.
N_STATS = 2


def init_moe_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    scale_in = d ** -0.5
    scale_out = f ** -0.5
    params = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) * scale_in,
        "w1": (jax.random.normal(k2, (e, d, f)) * scale_in).astype(dtype),
        "w3": (jax.random.normal(k3, (e, d, f)) * scale_in).astype(dtype),
        "w2": (jax.random.normal(k4, (e, f, d)) * scale_out).astype(dtype),
    }
    if cfg.shared_expert_d_ff:
        fs = cfg.shared_expert_d_ff
        params["shared"] = {
            "w1": (jax.random.normal(k5, (d, fs)) * scale_in).astype(dtype),
            "w3": (jax.random.normal(k6, (d, fs)) * scale_in).astype(dtype),
            "w2": (jax.random.normal(k7, (fs, d)) * fs ** -0.5).astype(dtype),
        }
    return params


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def route(params: dict, x: jax.Array, cfg: ModelConfig):
    """``x: (T, d)`` -> (softmax probabilities ``(T, E)``, gates and expert
    ids of each token's top ``k``, ``(T, k)``), in float32 at full matmul
    precision, so a near tie is broken as a float32 reference breaks it."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    return probs, gate, idx


def _experts(params: dict) -> tuple:
    """The routed experts' ``(w1, w3, w2)``, each ``(E, ...)``: the layer's
    own, or its slice of the stacks a scan passes with a ``layer`` index."""
    ws = tuple(params[k] for k in ("w1", "w3", "w2"))
    if "layer" in params:
        ws = tuple(w[params["layer"]] for w in ws)
    return ws


def _grouped(params: dict, rows: jax.Array, sizes: jax.Array,
             cfg: ModelConfig) -> jax.Array:
    """The gated expert MLP of ``rows`` sorted by expert, as grouped
    matmuls. Under a scan (``layer`` given) the weights stay whole stacks
    ``(G, E, ...)`` seen as ``G · E`` groups, every other layer's group
    empty: no slice of them is copied."""
    ws = [params[k] for k in ("w1", "w3", "w2")]
    if "layer" in params:
        g, e = ws[0].shape[:2]
        ws = [w.reshape(g * e, *w.shape[2:]) for w in ws]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((g * e,), jnp.int32), sizes, (params["layer"] * e,))
    act = _act(cfg.act)
    h = act(grouped_matmul(rows, ws[0], sizes)) * \
        grouped_matmul(rows, ws[1], sizes)
    return grouped_matmul(h, ws[2], sizes)


def _sc_experts(params: dict, rows: jax.Array, expert: jax.Array,
                sizes: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The grouped products through ``sc_proj``: rows laid out per expert at
    a capacity of every row (nothing can overflow), each expert's matmuls
    vmapped so it quantizes with its own per-tensor scale."""
    n, d = rows.shape
    e = cfg.n_experts
    start = jnp.cumsum(sizes) - sizes
    slot = jnp.arange(n) - start[expert]
    xe = jnp.zeros((e, n, d), rows.dtype).at[expert, slot].set(rows)
    dense = jax.vmap(lambda xr, w: sc_proj(xr, w, cfg))
    act = _act(cfg.act)
    w1, w3, w2 = _experts(params)
    h = act(dense(xe, w1)) * dense(xe, w3)
    return dense(h, w2)[expert, slot]


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``x: (B, S, d)`` -> (output, aux loss, load counters ``(N_STATS,)``
    int32)."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.top_k, cfg.n_experts
    xf = x.reshape(t, d)
    probs, gate, idx = route(params, xf, cfg)

    flat = idx.reshape(-1)                                   # (T·k,)
    order = jnp.argsort(flat, stable=True)                   # by expert
    expert = flat[order]
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    rows = xf[order // k]                                    # (T·k, d)
    if cfg.use_sc_gemm:
        y = _sc_experts(params, rows, expert, sizes, cfg)
    else:
        y = _grouped(params, rows, sizes, cfg)               # (T·k, d)
    y = y[jnp.argsort(order)].reshape(t, k, d)               # token order
    out = jnp.einsum("tk,tkd->td", gate, y.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, s, d)

    # --- Switch load-balance aux loss: E · Σ_e f_e · P_e
    top1 = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(top1.mean(axis=0) * probs.mean(axis=0))
    stats = jnp.stack([jnp.int32(t * k), sizes.max()]).astype(jnp.int32)

    if "shared" in params:
        sh = params["shared"]
        act = _act(cfg.act)
        hs = act(sc_proj(x, sh["w1"], cfg)) * sc_proj(x, sh["w3"], cfg)
        out = out + sc_proj(hs, sh["w2"], cfg)
    return out, aux, stats
