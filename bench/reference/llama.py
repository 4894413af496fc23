"""Plain reference of a Llama-shaped decoder (SmolLM and its kin), in float32.

Written from the published description (Llama: RMSNorm before attention and
MLP, rotary embeddings in the half-split convention, grouped-query attention
with ``n_heads / n_kv_heads`` query heads per key/value head, SiLU-gated MLP,
final RMSNorm, head tied to the embedding when the config says so). It
imports nothing of the program under test and is handed nothing the program
made: the weights come from :func:`make_params`, which the benchmark calls
with the run's seed, and the same function regenerates them here.

Three numerics for the dense projections, named by ``numerics`` in the
configuration file:

* ``exact``: ``x @ w`` in float32 at ``highest`` matmul precision;
* ``sc``: the paper's deterministic stochastic multiplier as the scalar
  product of every projection (:func:`sc_product`), at ``sc_bits``;
* ``fp8_weights``: weights rounded to float8 e4m3 with one abs-max scale
  per output channel — the control of a bfloat16 configuration, never the
  reference.

Attention (QK and PV) is exact float32 in every numeric.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Sizes", "make_params", "param_shapes", "sc_counts_pairwise",
           "sc_counts", "sc_product", "quantize", "logits_at"]


class Sizes:
    """The model's sizes, read from a configuration file's ``model`` group
    (Hugging Face key names)."""

    def __init__(self, model: dict, derived: dict):
        self.layers = int(model["num_hidden_layers"])
        self.d = int(model["hidden_size"])
        self.heads = int(model["num_attention_heads"])
        self.kv_heads = int(model["num_key_value_heads"])
        self.head_dim = int(derived["head_dim"])
        self.ff = int(model["intermediate_size"])
        self.vocab = int(model["vocab_size"])
        self.eps = float(model["rms_norm_eps"])
        self.theta = float(model["rope_theta"])
        self.tied = bool(model["tie_word_embeddings"])
        if not self.tied:
            raise ValueError("this reference implements tied embeddings only")
        if model["hidden_act"] != "silu":
            raise ValueError(f"unsupported activation {model['hidden_act']!r}")

    def key(self) -> tuple:
        return (self.layers, self.d, self.heads, self.kv_heads, self.head_dim,
                self.ff, self.vocab, self.eps, self.theta)


# ------------------------------------------------------------------ weights

def param_shapes(sizes_key: tuple) -> dict:
    """Shapes of the weight tree (from :meth:`Sizes.key`), in the layout the
    serving program takes: per-layer leaves stacked on a leading layer axis,
    attention weights with an explicit head axis, one layer per scanned
    group."""
    L, d, h, kv, hd, f, V = sizes_key[:7]
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": ({
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "mlp": {"w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)},
        },),
    }


def _fan_in(path: str, shape: tuple) -> int:
    if path.endswith("wo"):
        return shape[1] * shape[2]
    return shape[-1] if path == "embed" else shape[1]


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _make(seed_key, sizes_key, dtype):
    shapes = param_shapes(sizes_key)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    keys = jax.random.split(seed_key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if "norm" in name or name.endswith(("ln1", "ln2")):
            # norm gains near 1 but not 1, so a gain left unapplied shows
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = (jax.random.normal(k, shape, jnp.float32)
                 * _fan_in(name, shape) ** -0.5)
        out.append(w.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    is folded through numpy's SeedSequence)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def make_params(sizes: Sizes, seed: int, dtype=jnp.bfloat16):
    """The weight tree for ``seed``, made on the default device in one
    jitted call, in ``dtype`` (the served type)."""
    return _make(seed_key(seed), sizes.key(), jnp.dtype(dtype))


# --------------------------------------------------------------- numerics

def quantize(v: jax.Array, bits: int, axis):
    """Abs-max sign-magnitude quantization: ``v ≈ sign · mag · scale`` with
    ``mag`` an integer in ``[0, 2**bits - 1]``; ``axis=None`` gives one scale
    for the tensor, ``axis=-1`` one per row."""
    n_max = 2 ** bits - 1
    absmax = jnp.max(jnp.abs(v), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(absmax, 1e-12).astype(jnp.float32) / n_max
    mag = jnp.clip(jnp.round(jnp.abs(v) / scale), 0, n_max).astype(jnp.int32)
    sign = jnp.where(v < 0, -1, 1).astype(jnp.int32)
    return sign, mag, scale


def sc_counts_pairwise(sx, mx, sy, my, bits: int) -> jax.Array:
    """Σ_k s_x s_y · O(x, y), one pair at a time: the paper's closed form of
    the bit-parallel multiplier for B-bit magnitudes ``x, y``,

        O(x, y) = msb_y · ⌊x/2⌋ + max(min(y_low, ⌊(x − msb_y)/2⌋), 0),

    where ``msb_y`` is the top bit of ``y`` and ``y_low`` the rest.
    ``(M, K)`` and ``(K, N)`` operands give ``(M, N)`` int32 counts. Builds
    the whole ``(M, K, N)`` broadcast, so it serves small shapes and tests."""
    half = 2 ** bits // 2
    x = mx[:, :, None]
    y = my[None, :, :]
    msb = (y >= half).astype(jnp.int32)
    y_low = y - msb * half
    o = msb * (x // 2) + jnp.maximum(jnp.minimum(y_low, (x - msb) // 2), 0)
    s = sx[:, :, None] * sy[None, :, :]
    return (s * o).sum(axis=1, dtype=jnp.int32)


def _idot(a, b):
    return jax.lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def sc_counts(sx, mx, sy, my, bits: int) -> jax.Array:
    """The same counts as :func:`sc_counts_pairwise`, as integer matrix
    products. The first term is ``(s_x ⌊x/2⌋) @ (s_y msb_y)``. The clamped
    minimum is unary: for ``a, b ≥ 0``, ``min(a, b) = Σ_{t≥1} [a ≥ t][b ≥ t]``
    (and a negative ``⌊(x − 1)/2⌋`` contributes no term), so the second term
    is a sum over thresholds ``t`` of products of 0/±1 matrices, split by
    ``msb_y``. Every operand fits int8 and every sum int32: exact."""
    half = 2 ** bits // 2
    msb = (my >= half).astype(jnp.int32)
    y_low = my - msb * half
    counts = _idot(sx * (mx // 2), sy * msb)
    z = jnp.concatenate([mx // 2, (mx - 1) // 2], axis=1)          # (M, 2K)
    sxx = jnp.concatenate([sx, sx], axis=1)
    ym = jnp.concatenate([sy * (1 - msb), sy * msb], axis=0)       # (2K, N)
    yl = jnp.concatenate([y_low, y_low], axis=0)

    def body(acc, t):
        return acc + _idot(sxx * (z >= t), ym * (yl >= t)), None

    counts, _ = jax.lax.scan(body, counts, jnp.arange(1, half))
    return counts


def sc_product(a: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """``a @ b`` through the multiplier: per-row scales for the activations
    ``a``, one scale for the weights ``b``, exact integer accumulation."""
    sx, mx, sa = quantize(a, bits, axis=-1)
    sy, my, sb = quantize(b, bits, axis=None)
    counts = sc_counts(sx, mx, sy, my, bits)
    return counts.astype(jnp.float32) * (2 ** bits * sa * sb)


def _fp8_weights(w: jax.Array) -> jax.Array:
    """``w`` rounded to float8 e4m3 with one abs-max scale per output
    column (the column's largest magnitude maps to e4m3's largest, 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 448
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _projector(numerics: str, bits: int):
    if numerics == "exact":
        return lambda x, w: jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    if numerics == "sc":
        return lambda x, w: sc_product(x, w, bits)
    if numerics == "fp8_weights":
        return lambda x, w: jnp.dot(x, _fp8_weights(w),
                                    precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown numerics {numerics!r}")


# ----------------------------------------------------------------- forward

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]   # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes_key", "numerics", "bits"))
def _logits_at(params, tokens, rows, sizes_key, numerics, bits):
    L, d, h, kv, hd, f, V, eps, theta = sizes_key
    proj = _projector(numerics, bits)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, s = tokens.shape
    g = h // kv
    x = p["embed"][tokens]                                   # (B, S, d)
    positions = jnp.arange(s)
    causal = positions[:, None] >= positions[None, :]

    def dense(x, w):
        return proj(x.reshape(-1, x.shape[-1]), w).reshape(
            *x.shape[:-1], w.shape[-1])

    def layer(x, lp):
        a = _rms(x, lp["ln1"], eps)
        q = dense(a, lp["attn"]["wq"].reshape(d, h * hd)).reshape(b, s, h, hd)
        k = dense(a, lp["attn"]["wk"].reshape(d, kv * hd)).reshape(b, s, kv, hd)
        v = dense(a, lp["attn"]["wv"].reshape(d, kv * hd)).reshape(b, s, kv, hd)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        q = q.reshape(b, s, kv, g, hd)
        sc = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", pr, v,
                       precision=jax.lax.Precision.HIGHEST).reshape(b, s, h * hd)
        x = x + dense(o, lp["attn"]["wo"].reshape(h * hd, d))
        m = _rms(x, lp["ln2"], eps)
        up = jax.nn.silu(dense(m, lp["mlp"]["w1"])) * dense(m, lp["mlp"]["w3"])
        return x + dense(up, lp["mlp"]["w2"]), None

    x, _ = jax.lax.scan(layer, x, p["layers"][0])
    x = _rms(x, p["final_norm"], eps)
    sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)    # (B, R, d)
    return dense(sel, p["embed"].T)                           # (B, R, V)


def logits_at(params, tokens, rows, sizes: Sizes, numerics: str = "exact",
              bits: int = 8) -> jax.Array:
    """Next-token logits ``(B, R, V)`` at positions ``rows: (B, R)`` of the
    sequences ``tokens: (B, S)``, from a full causal forward. Rows past a
    sequence's real length read padding and are the caller's to ignore."""
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), sizes.key(), numerics,
                      int(bits))
