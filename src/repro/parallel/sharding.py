"""Logical-axis sharding rules: params, batches, and decode caches.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod. Policy (DESIGN.md §3):

* **TP** over ``model``: attention QKV/O, MLP d_ff, vocab/embedding, experts.
* **FSDP (ZeRO-3)** over ``data``: every matrix's other large dim. Weights are
  *replicated* across pods — cross-pod traffic is gradient all-reduce only,
  which is what int8 gradient compression then targets.
* Batch over ``("pod", "data")``; decode caches shard batch and either KV
  heads (if divisible by the model-axis size) or head_dim over ``model``.
  ``long_500k`` (batch=1) shards the cache's *sequence* axis over ``data``.

Only params, step inputs/outputs, and caches are constrained; interior
activation shardings propagate via GSPMD.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

__all__ = ["param_pspecs", "batch_pspecs", "cache_pspecs", "slot_pool_pspecs",
           "paged_pool_pspecs", "paged_tables_pspec", "named", "DATA_AXES"]

DATA_AXES = ("pod", "data")          # batch / FSDP axes (pod may be absent)


def _axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def _data_axis(mesh: Mesh):
    return tuple(a for a in DATA_AXES if a in _axes(mesh)) or None


def _fsdp_axis(mesh: Mesh):
    # FSDP over "data" only (pods replicate weights; see module docstring)
    return "data" if "data" in _axes(mesh) else None


def _key_of(path_entry) -> str:
    if hasattr(path_entry, "key"):
        return str(path_entry.key)
    if hasattr(path_entry, "name"):          # GetAttrKey (NamedTuple fields)
        return str(path_entry.name)
    if hasattr(path_entry, "idx"):
        return str(path_entry.idx)
    return str(path_entry)


def _axis_size(mesh: Mesh, axis) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(axis, 1)


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop partitioning on any dim the axis size does not evenly divide —
    jit input shardings (unlike interior constraints) cannot pad."""
    out = []
    for i, axis in enumerate(spec):
        if axis is None or i >= len(shape):
            out.append(None if i >= len(shape) else axis)
            continue
        out.append(axis if shape[i] % _axis_size(mesh, axis) == 0 else None)
    return P(*out[: len(shape)])


def _spec_for(path: tuple, leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    keys = [_key_of(p) for p in path]
    name = keys[-1]
    in_layers = "layers" in keys or "lead" in keys   # stacked layer params
    fsdp = _fsdp_axis(mesh)
    ndim = leaf.ndim - (1 if in_layers else 0)   # strip stacked group dim

    def wrap(*spec):
        spec = spec + (None,) * (ndim - len(spec))
        if in_layers:
            spec = (None,) + spec                # group/stack dim replicated
        return P(*spec)

    # ---- embeddings / head
    if name == "embed":
        if cfg.n_codebooks:                      # (K, V, d)
            return P(None, "model", fsdp)
        return P("model", fsdp)                  # (V, d)
    if name == "lm_head":
        return P(fsdp, "model")                  # (d, V)

    # ---- norms, scalars, biases on d_model
    if name.startswith("ln") or name in ("final_norm", "gate_norm", "q_norm",
                                         "k_norm", "dt_bias", "A_log", "D",
                                         "conv_b"):
        return wrap()
    if name in ("bq", "bk", "bv"):
        return wrap("model", None)            # (heads, head_dim)

    # ---- MoE experts (E, d, f) / (E, f, d); router (d, E)
    if "moe" in keys and name in ("w1", "w3"):
        return wrap("model", fsdp, None)
    if "moe" in keys and name == "w2":
        return wrap("model", None, fsdp)
    if name == "router":
        return wrap(fsdp, None)

    # ---- attention projections: (d, heads, head_dim) / (heads, head_dim, d).
    # Heads shard over "model" when divisible; otherwise shard head_dim
    # (always 16-divisible for the assigned archs) — Megatron would replicate
    # KV instead, but head_dim sharding keeps TP on the big Q/O projections.
    model_size = _axis_size(mesh, "model")
    if name in ("wq", "wk", "wv"):
        n_heads = leaf.shape[-2]
        if n_heads % model_size == 0:
            return wrap(fsdp, "model", None)
        return wrap(fsdp, None, "model")
    if name == "wo":
        n_heads = leaf.shape[-3] if not in_layers else leaf.shape[1]
        if n_heads % model_size == 0:
            return wrap("model", None, fsdp)
        return wrap(None, "model", fsdp)

    # ---- dense projections
    if name in ("w1", "w3", "in_proj"):
        return wrap(fsdp, "model")               # (d, out)
    if name in ("w2", "out_proj"):
        return wrap("model", fsdp)               # (in, d)
    if name == "conv_w":
        return wrap(None, "model")               # (width, channels)

    return wrap()                                # fallback: replicate


def _strip_model(spec: P) -> P:
    """DP-only strategy: drop the model axis from a spec (pure FSDP layout —
    the §Perf answer for models too small to amortize TP/SP collectives)."""
    def strip(axis):
        if axis == "model":
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if a != "model")
            return kept if kept else None
        return axis
    return P(*(strip(a) for a in spec))


def param_pspecs(cfg: ModelConfig, params: Any, mesh: Mesh) -> Any:
    dp_only = getattr(cfg, "sharding_strategy", "tp_sp") == "dp"

    def one(path, leaf):
        spec = _spec_for(path, leaf, cfg, mesh)
        if dp_only:
            spec = _strip_model(spec)
        return fit_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_pspecs(cfg: ModelConfig, batch: Any, mesh: Mesh) -> Any:
    data = _data_axis(mesh)
    if getattr(cfg, "sharding_strategy", "tp_sp") == "dp":
        all_axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
        if "model" in mesh.axis_names:
            all_axes = all_axes + ("model",)
        data = all_axes or None

    def spec(path, leaf):
        name = _key_of(path[-1])
        if name == "mrope_positions":            # (3, B, S)
            return fit_spec(P(None, data), leaf.shape, mesh)
        return fit_spec(P(data), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, batch)


def _seq_sharded(cfg: ModelConfig, batch_size: int, mesh: Mesh) -> bool:
    """long_500k: batch too small for the data axis -> shard sequence instead."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_size = int(np.prod([sizes[a] for a in DATA_AXES if a in sizes]))
    return batch_size < data_size


def cache_pspecs(cfg: ModelConfig, cache: Any, mesh: Mesh, *,
                 batch_size: int) -> Any:
    data = _data_axis(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)
    kv_shardable = cfg.n_kv_heads % model_size == 0
    seq_mode = _seq_sharded(cfg, batch_size, mesh)

    def spec(path, leaf):
        name = _key_of(path[-1])
        if leaf.ndim == 0:
            return P()
        if name == "pos":                        # per-sequence (B,) positions
            raw = P(data if not seq_mode else None)
        elif name in ("k", "v") or (len(path) >= 2 and _key_of(path[-2]) in ("k", "v")):
            # (stack, B, S, KV, hd)
            if seq_mode:
                raw = P(None, None, "data", None, "model")
            elif kv_shardable:
                raw = P(None, data, None, "model", None)
            else:
                raw = P(None, data, None, None, "model")
        elif name == "state":                    # mamba (L, B, H, P, N)
            raw = P(None, data if not seq_mode else None, "model")
        elif name == "conv":                     # (L, B, width, channels)
            raw = P(None, data if not seq_mode else None, None, "model")
        else:
            raw = P()
        return fit_spec(raw, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, cache)


def slot_pool_pspecs(cfg: ModelConfig, cache: Any, mesh: Mesh, *,
                     capacity: int) -> Any:
    """Cache pspecs for a serving *slot pool* (DESIGN.md §7).

    A slot pool is structurally a decode cache whose batch axis is the fixed
    slot capacity, so slots shard exactly like batch: the slot axis spreads
    over the data axes and KV heads / head_dim / SSM heads over ``model``,
    and the per-slot ``pos`` vector follows the slot axis. Admission and
    eviction (``models.cache_ops``) are slot-axis scatters, which GSPMD
    keeps local to the shard that owns the slot.
    """
    return cache_pspecs(cfg, cache, mesh, batch_size=capacity)


def paged_pool_pspecs(cfg: ModelConfig, cache: Any, mesh: Mesh) -> Any:
    """Cache pspecs for a *paged* serving pool (DESIGN.md §8).

    Sequence (k/v) leaves are ``(lead, n_blocks + 1, block, KV, hd)``: the
    page axis stays **unsharded** — page allocation is host-driven (the
    engine's free list hands out arbitrary physical ids), so pages must stay
    addressable from the host exactly like slots in the contiguous pool
    (ROADMAP's multi-host item covers lifting both). TP instead shards KV
    heads — or head_dim when the head count doesn't divide the model axis —
    so every page splits the same way and gather/scatter through the block
    table stays shard-local along the model axis — the same invariant the
    fused kernel's in-kernel table walk relies on (§9; see
    :func:`paged_tables_pspec`). Slot leaves (SSM state / conv) likewise
    keep the slot axis unsharded and shard channels over ``model``.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)
    kv_shardable = cfg.n_kv_heads % model_size == 0

    def spec(path, leaf):
        name = _key_of(path[-1])
        if leaf.ndim == 0 or name == "pos":
            return P()
        if name in ("k", "v") or (len(path) >= 2
                                  and _key_of(path[-2]) in ("k", "v")):
            raw = P(None, None, None, "model", None) if kv_shardable \
                else P(None, None, None, None, "model")
        elif name == "state":                    # mamba (L, C, H, P, N)
            raw = P(None, None, "model")
        elif name == "conv":                     # (L, C, width, channels)
            raw = P(None, None, None, "model")
        else:
            raw = P()
        return fit_spec(raw, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, cache)


def paged_tables_pspec(mesh: Mesh) -> P:
    """PartitionSpec for the ``(capacity, max_blocks)`` block tables.

    Fully replicated, deliberately: the tables are tiny (a few KiB), but —
    more to the point — the fused paged-attention kernel (DESIGN.md §9)
    scalar-prefetches the *whole* table on every shard to drive its
    in-kernel page walk, and the jnp fallback's gather indexes it the same
    way. The pool's page axis is likewise unsharded (``paged_pool_pspecs``),
    so a table entry means the same physical page on every shard and the
    walk only ever touches shard-local bytes along ``model`` (KV heads /
    head_dim split identically across every page). Sharding either axis of
    the table would force a pre-kernel all-gather and break that locality.
    """
    del mesh
    return P(None, None)


def named(mesh: Mesh, pspecs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
